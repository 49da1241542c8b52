"""paged_attn_roofline.decode-heavy"""
from bench import readers


def read(rec):
    return readers.paged_attn_roofline(rec)
