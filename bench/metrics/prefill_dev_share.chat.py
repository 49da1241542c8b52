"""prefill_dev_share.chat"""
from bench import readers


def read(rec):
    return readers.program_share(rec, "prefill")
