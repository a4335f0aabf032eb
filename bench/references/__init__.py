"""Plain references, one module per architecture a configuration names."""
