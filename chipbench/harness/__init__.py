"""The benchmark's yardstick: data generation, the plain reference, the
work model, the closed-loop client, trace reduction and the cell lookup.

Nothing here is imported by the program; the program is imported only by
``client`` (the system under test) and ``spans`` (its span hook)."""
