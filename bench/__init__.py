"""Chip benchmark of the scheduling service and the FL trainers."""
