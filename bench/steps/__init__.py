"""Request kinds, one module per kind, named by a traffic mix's ``steps``."""
