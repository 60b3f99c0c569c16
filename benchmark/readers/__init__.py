"""One small reader a kind of metric: ``read(run, **params)`` returns the
number, or None where it finds nothing to read (the harness then leaves
the metric out of the line; it never reports 0 for a share)."""
