"""The benchmark's own machinery: discovery by name, clocks and fenced
segments, the open-loop generator, the program counter, the profiler
window and the result line.  Nothing here is specific to one
configuration, one traffic mix or one per-layer metric — those live in
files of their own that :mod:`znbench.harness.discovery` finds by the
names ``BENCHMARK.json`` gives."""
