"""Plain references, one per app model, that import nothing of the program
under test."""
