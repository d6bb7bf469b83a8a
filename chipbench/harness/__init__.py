"""The benchmark harness: what every cell shares (device check, weights,
traffic, drivers, trace reduction, correctness comparison)."""
