"""The chip benchmark: ``python bench/run.py --workload <name> ...``."""
