"""The yardstick: everything here is the benchmark's own and imports
nothing of the program (``veles_tpu``)."""
