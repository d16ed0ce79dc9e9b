"""Traffic drivers, one per kind of loop, found by name."""
