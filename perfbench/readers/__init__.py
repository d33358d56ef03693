"""One module per kind of per-layer reader: ``read(spec, facts)`` returns
the metric's value, or None where there is nothing to read."""
