"""mfu.decode-heavy"""
from bench import readers


def read(rec):
    return readers.mfu(rec)
