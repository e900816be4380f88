"""Plain references: independent of the program, rebuilt from the seed."""
