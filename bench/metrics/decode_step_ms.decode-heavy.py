"""decode_step_ms.decode-heavy"""
from bench import readers


def read(rec):
    return readers.decode_step_ms(rec)
