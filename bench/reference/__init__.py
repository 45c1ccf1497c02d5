"""Plain float32 references of the served models, independent of the program."""
