"""paged_attn_roofline.chat"""
from bench import readers


def read(rec):
    return readers.paged_attn_roofline(rec)
