"""The chip benchmark's yardstick: traffic, weights, the float32
reference, shape-based operation counts, trace reduction and the run
harness.  ``run_cell.py`` beside this package is the entry point."""
