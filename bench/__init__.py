"""The chip benchmark: cells, traffic, reference and metric readers."""
