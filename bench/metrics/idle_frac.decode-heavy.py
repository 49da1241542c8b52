"""idle_frac.decode-heavy"""
from bench import readers


def read(rec):
    return readers.idle_frac(rec)
