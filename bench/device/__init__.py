"""Device trace reduction, peak table and work counts."""
