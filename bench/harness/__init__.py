"""The chip benchmark's own code: cell lookup, traffic, load generation,
counting functions, trace reduction and the check.  The plain references
it compares against are files of their own under ``bench/references/``,
found by the name a configuration gives.

Nothing here is imported by the program under test, and the load
generator (``loadgen``) and ``traffic`` never import JAX."""
