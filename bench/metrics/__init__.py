"""One reader per metric, found by name."""
