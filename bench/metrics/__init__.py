"""Per-layer metric readers, one file per metric, found by the metric's
name; ``roofline_bytes`` holds the bytes an operation must move."""
