"""setup_s"""
from bench import readers


def read(rec):
    return readers.setup_s(rec)
