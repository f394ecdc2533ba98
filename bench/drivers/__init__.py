"""Drivers: one module per kind of cell, named by a configuration's
``driver`` key."""
