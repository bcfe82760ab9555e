"""Drivers: one per kind of traffic, named by a mix's ``driver`` key."""
