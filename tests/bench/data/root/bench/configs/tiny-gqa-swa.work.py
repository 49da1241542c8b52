"""Work of the dense decoder in which every layer attends over a sliding
window: ``bench/work.py``'s counts with each query reading
``min(keys so far, cfg.sliding_window)`` keys, and a decode row's K and V
read only for those keys."""

from bench import work as dense


def prefill_flops(cfg, prompt_len):
    s, w = prompt_len, cfg.sliding_window
    keys = s * (s + 1) // 2 if s <= w else w * (w + 1) // 2 + (s - w) * w
    attn = 4 * cfg.n_heads * cfg.head_dim * keys
    return (cfg.n_layers * (2 * dense.layer_matmul_params(cfg) * s + attn)
            + 2 * dense.head_params(cfg))


def decode_flops(cfg, length):
    return dense.decode_flops(cfg, min(length, cfg.sliding_window))


def paged_attention_work(cfg, lengths, page_size, kv_itemsize):
    return dense.paged_attention_work(
        cfg, [min(n, cfg.sliding_window) for n in lengths], page_size,
        kv_itemsize)
