"""The chip benchmark's yardstick: traffic, weights, reference, FLOP
counts, peaks and trace reduction.  ``run.py`` beside this package is the
command."""
