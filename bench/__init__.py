"""The benchmark of the distance-threshold query engine on the chip.

``bench/run.py`` runs one cell; ``bench/README.md`` says how the parts are
found by name and how to add one.
"""
