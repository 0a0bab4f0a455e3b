"""One small reader per *kind* of source; a per-layer metric's file under
``layer_metrics/`` names its kind and the keys the reader takes.  A reader
returns ``None`` when it finds nothing to read, and the harness then leaves
the metric out of the line."""
