"""The chip benchmark's own code: cell lookup, traffic, load generation,
counting functions, trace reduction, the plain reference and the check.

Nothing here is imported by the program under test, and the load
generator (``loadgen``) and ``traffic`` never import JAX."""
