"""Plain reference of the dense decoder in which every layer attends over
a sliding window: ``bench/reference.py`` with each query position reading
its last ``cfg.sliding_window`` keys, itself included."""

from bench import reference as dense


def windowed(rows, cols, cfg):
    return (dense.causal(rows, cols, cfg)
            & (cols[None, :] > rows[:, None] - cfg.sliding_window))


def served_gaps(params, cfg, served, pad_to, control=False):
    return dense.served_gaps(params, cfg, served, pad_to, control,
                             mask=windowed)
