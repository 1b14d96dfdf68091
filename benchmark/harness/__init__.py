"""The general part of the benchmark: nothing in here names a cell, a
configuration, a traffic mix or a metric.  Those are files beside this
directory, found by name (see ../README.md)."""

import os

# benchmark/: where the data files live
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
