"""mfu.chat"""
from bench import readers


def read(rec):
    return readers.mfu(rec)
