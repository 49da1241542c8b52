"""idle_frac.chat"""
from bench import readers


def read(rec):
    return readers.idle_frac(rec)
