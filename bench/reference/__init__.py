"""The plain reference of filtered search, in NumPy (imports nothing of
the program)."""
