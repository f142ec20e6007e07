"""Seconds from process start to the window's start: data generation, the
program's bulk load, compile-cache loads and warm-up."""


def read(rec):
    return rec.setup_s
