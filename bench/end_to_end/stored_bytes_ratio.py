"""Object bytes the window's commits wrote to the store's directory, as the
harness finds them on disk, over the raw bytes of those commits."""


def read(w):
    raw = sum(r.nbytes for r in w.of("commit"))
    return w.stored_bytes / raw if raw else None
