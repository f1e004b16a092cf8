"""System drivers: one module per kind of system under test.

A configuration file names its driver by its ``system`` key; each module
exports ``Cell(spec, seed)`` with ``setup``, ``window``, ``end_to_end``,
``layer_data``, ``release``, ``check``, ``control_check`` and
``attempted_failed``.
"""
