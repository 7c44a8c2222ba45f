"""``chem_enumerate_ms.serve`` in the overload cell, where it moves the
completed rate rather than the tail."""

from chip import harness

read = harness.metric_reader("chem_enumerate_ms.serve")
