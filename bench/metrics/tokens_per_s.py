"""tokens_per_s: tokens delivered to the host in the window (each request's
up to its drawn length), over the window's seconds.  Host clock."""


def read(rec):
    return rec["tokens_in_window"] / rec["window_s"]
