"""repro: FOS-on-JAX reproduction package."""
