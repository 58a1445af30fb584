"""builders of the PIQUE benchmark, each found by name."""
