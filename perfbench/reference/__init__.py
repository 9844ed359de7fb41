"""Plain references, one per family of configurations."""
