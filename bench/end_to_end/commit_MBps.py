"""Raw bytes of every commit acknowledged in the window, in MB, over the
window's seconds."""


def read(w):
    commits = w.of("commit")
    return sum(r.nbytes for r in commits) / w.seconds / 1e6 if commits else None
