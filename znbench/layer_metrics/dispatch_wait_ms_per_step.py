"""Host time inside the calls that hand the device a warmed program,
per training step: the summed duration of the program's cat ``region``
spans (``dispatch:<region>`` per step, ``chunk:<region>`` /
``accum:<region>`` per scanned dispatch, all opened at
``JitRegion._dispatch``) that lie inside the window ÷ steps.  Past the
few programs the runtime admits in flight such a call BLOCKS until the
device finishes one, so near ``step_device_ms`` the device is the
limit and near 0 the host is; ``host_busy_ms_per_step`` leaves these
spans out on purpose.  Nothing where the program records no such
span."""

from znbench.harness import discovery


def read(obs):
    steps = obs.observations.get("steps")
    in_window = discovery.load_module(
        "layer_metrics", "host_reads_per_step").in_window
    calls = [s for s in in_window(obs) if s["cat"] == "region"]
    if not steps or not calls:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in calls) / steps
