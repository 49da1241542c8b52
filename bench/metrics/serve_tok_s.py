"""serve_tok_s"""
from bench import readers


def read(rec):
    return readers.serve_tok_s(rec)
