"""The host's time in copies up to the device during set-up: the
summed ``upload:<vector>`` spans (``Vector._upload``) that begin
inside a root ``initialize:<workflow>`` span — a PART of
``setup_initialize_s``.  It is the host's time in the call and no
fence: what is left of a copy when ``put`` returns is waited for by
whoever next blocks on the device.  The counter beside the span is
``znicz_setup_seconds{phase="upload"}``."""

from znbench.harness import discovery


def read(obs):
    return discovery.load_module(
        "layer_metrics", "setup_initialize_s").row(obs, "upload")
