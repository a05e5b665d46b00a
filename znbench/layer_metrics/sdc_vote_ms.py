"""Median duration of the SDC sentinel's votes in the window (the
program's ``sdc_vote`` spans: every parameter read back and
fingerprinted on the host while the device idles, once per
``engine.sdc_vote_interval`` decision ticks).  Left out where no vote
fell in the window."""

import statistics

from znbench.harness import discovery


def read(obs):
    in_window = discovery.load_module(
        "layer_metrics", "host_reads_per_step").in_window
    votes = [s["t1"] - s["t0"] for s in in_window(obs)
             if s["name"] == "sdc_vote"]
    return 1e3 * statistics.median(votes) if votes else None
