"""The chip benchmark: harness, store, trace reduction and its own TPC-H
data, queries and references.  ``bench/run.py`` is the command."""
