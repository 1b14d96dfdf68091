"""One module per kind of traffic, found by the mix's ``kind``: each has
one ``run(run)`` that builds the system, holds the window, checks the
outputs and returns ``{"window", "trace", "attempted", "failed",
"problems"}``.  A new kind of traffic is a new module here."""
