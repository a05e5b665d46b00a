"""The 99th percentile of the gap between two tokens of a lane, from
the engine's own sliding window (``stats()["token_ms"]``, the last
4096 gaps): the median over the samples taken during the window.  The
client cannot time single gaps until the future carries a token
timestamp."""

import statistics


def read(obs):
    seen = [s["token_ms"]["p99"] for s in
            obs.observations.get("samples") or [] if s["token_ms"]]
    return statistics.median(seen) if seen else None
