"""decode_rows_mean.chat"""
from bench import readers


def read(rec):
    return readers.decode_rows_mean(rec)
