"""The on-chip benchmark: cells, jobs, references and trace reduction.

``run.py`` beside this package is the entry point; ``harness`` runs one
cell once.  Nothing here is imported by the program under test.
"""
