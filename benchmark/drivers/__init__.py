"""Drivers: one module per kind of cell (``fit``, ``transform``), found
by the ``driver`` key of the cell's file. Only these modules import the
program, and only its public entry points."""
