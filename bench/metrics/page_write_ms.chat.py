"""page_write_ms.chat"""
from bench import spans


def read(rec):
    return spans.page_write_ms(rec)
