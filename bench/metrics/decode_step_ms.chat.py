"""decode_step_ms.chat"""
from bench import readers


def read(rec):
    return readers.decode_step_ms(rec)
