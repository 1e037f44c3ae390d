"""The benchmark's own yardstick: harness, trace reduction, peaks,
compile counting.  Later PRs to the program cannot change these."""
