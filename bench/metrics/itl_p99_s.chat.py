"""itl_p99_s.chat"""
from bench import readers


def read(rec):
    return readers.itl_quantile(rec, 0.99)
