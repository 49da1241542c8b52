"""ttft_p90_s"""
from bench import readers


def read(rec):
    return readers.ttft_quantile(rec, 0.90)
