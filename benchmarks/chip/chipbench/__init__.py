"""The chip benchmark's yardstick: manifest and file lookup, weights and
data from the seed, the plain reference, FLOP and byte counts, and the
reduction of profiler traces to metrics.  Nothing here is imported by the
program under test."""
