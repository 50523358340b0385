"""Seconds that set-up spent in the trainer's blocking bootstrap save
(the program's ``train.ckpt`` span before the window, which holds its
``ckpt.gather``, ``write``, ``crc`` and ``commit``)."""


def read(r):
    s = r["setup_spans"].get("train.ckpt")
    return s[0] if s else None
