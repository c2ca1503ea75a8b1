"""The yardstick: traffic, statistics, peaks, FLOP and byte arithmetic, the
trace reduction and the plain reference.  Later PRs add files beside these
and never edit them (perf/README.md)."""
