"""One reader per per-layer metric, each in a file named after it."""
