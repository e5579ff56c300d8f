"""One builder and work count per configuration, found by name."""
