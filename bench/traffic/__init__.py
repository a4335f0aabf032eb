"""Traffic mixes: ``<mix>.json`` data files read by ``generator``."""
