"""One module per reader, found by the ``reader`` a metric's file names.
``read(run, **args)`` takes the number from the run's window (the
benchmark's own stamps and the program's counters) or from its trace,
and returns None where there is nothing to read: the metric is then
left out of the line."""
