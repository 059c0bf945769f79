"""Adapters between the harness and the program under test, one module
per kind of system (a configuration's ``system`` key names it)."""
