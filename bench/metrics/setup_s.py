"""Process start to the end of warm-up: weights, engine, compilation.  A
backlog's steps that seat its first slots before the window are not
set-up; the run prints their count and seconds on standard error."""


def read(rec):
    return rec.setup_s
