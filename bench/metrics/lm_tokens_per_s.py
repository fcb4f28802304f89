"""Output tokens emitted inside the window, over the window, in tokens/s
(``serving_lm_tokens_total``, read at the window's end)."""


def read(rec):
    n = rec.get("lm_tokens_in_window")
    return n / rec["seconds"] if n is not None else None
