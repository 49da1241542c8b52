"""decode_rows_mean.decode-heavy"""
from bench import readers


def read(rec):
    return readers.decode_rows_mean(rec)
