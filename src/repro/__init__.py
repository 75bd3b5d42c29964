"""BigDataSDNSim reproduction as a jax tensor program."""
