"""Store commit: mean duration of the store's ``store.commit`` spans in the
window, in milliseconds."""


def read(w):
    d = [s.duration for s in w.spans if s.name == "store.commit"]
    return sum(d) / len(d) * 1e3 if d else None
