"""What the host must do per step before the device has its next
program: the SELF time of the program's ``unit`` spans (loader
bookkeeping, the guard's arming, the region unit's preparation of a
dispatch, the decision) over the window's steps.  Self time is a
span's duration minus its children's, the children found by
``parent_span_id``.  Three kinds of children are of no counted
category, so their time is not in here and nothing is counted twice:
the blocking reads (``host_read:*``, cat ``transfer``; waiting, in
``host_read_wait_ms_per_step``), the SDC vote (``sdc_vote``, cat
``resilience``; in ``sdc_vote_ms``), and the call that hands the
device a warmed program (``dispatch:<region>`` per step,
``chunk:<region>`` / ``accum:<region>`` per scanned dispatch, cat
``region``): the runtime admits a few programs in flight and a call
beyond them waits for the device inside that span, which is not work.
Well under ``step_device_ms`` the host can run ahead.  Nothing where
the spans carry no parents."""

import collections

from znbench.harness import discovery

COUNTED = ("unit",)


def read(obs):
    steps = obs.observations.get("steps")
    in_window = discovery.load_module(
        "layer_metrics", "host_reads_per_step").in_window
    spans = [s for s in in_window(obs)
             if "span_id" in s["args"] and "trace_id" not in s["args"]]
    if not steps or not spans:
        return None
    children: collections.Counter = collections.Counter()
    for span in spans:
        children[span["args"]["parent_span_id"]] += \
            span["t1"] - span["t0"]
    busy = sum(
        max(0.0, s["t1"] - s["t0"] - children[s["args"]["span_id"]])
        for s in spans if s["cat"] in COUNTED)
    return 1e3 * busy / steps
