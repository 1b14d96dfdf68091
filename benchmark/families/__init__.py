"""One adapter per model family, found by the ``family`` a configuration
file names.  An adapter turns the file's published keys into the
program's own config object, makes weights from a seed inside one jit,
and owns the family's arithmetic: parameters, FLOPs a token, bytes a
decode step must read, and the plain reference that judges ``correct``.
"""
