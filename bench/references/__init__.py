"""Plain references, named by a configuration's ``reference`` key."""
