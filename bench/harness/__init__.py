"""The benchmark's yardstick: traffic, weights, reference, counts, trace
reduction. Nothing here imports the program except ``program.py``, which
builds the system under test."""
