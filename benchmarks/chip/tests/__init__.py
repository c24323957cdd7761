"""CPU tests of the chip benchmark (its harness, reductions and checks)."""
