"""The benchmark's general code: loading cells by name, the cell drivers,
the trace reduction and the result line.  Everything that belongs to one
configuration, traffic mix or per-layer metric lives in its own file under
``configs/``, ``traffic/`` or ``metrics/`` and is found by name."""
