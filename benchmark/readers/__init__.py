"""One small reader per kind of per-layer metric.  `read(run, params)` takes
the number from the run's spans, counters, logs or trace summary; a reader
that finds nothing to read returns None and the metric is left out."""
