"""Set-up before the program begins: from the OS's start of the process
to the first root ``initialize:<workflow>`` span's opening — the
interpreter, the imports of JAX and the package, the TPU runtime
reaching the chip, and what the caller does before it initializes (the
drivers draw their data there).  A row of the partition in
``setup_initialize_s.py``; nothing where that has nothing to read."""

from znbench.harness import discovery


def read(obs):
    return discovery.load_module(
        "layer_metrics", "setup_initialize_s").row(obs, "preprogram")
