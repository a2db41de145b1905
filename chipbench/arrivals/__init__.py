"""Arrival kinds: ``<kind>.py`` is the traffic files' ``"arrivals": "<kind>"``."""
