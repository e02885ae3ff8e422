"""Host time per absorbed upload spent in the cohort engine itself: the
window's seconds less the time inside ``receive`` calls and inside
``cohort_train_encode_step`` calls, per upload. It covers admission, the
data provider, key splits, the wait for each cohort's codes while they are
framed, and the replicas' decode of every broadcast."""


def read(ctx):
    w = ctx.window
    if not w.uploads:
        return None
    inside = sum(w.receive_s) + sum(w.flush_s) + sum(w.step_s)
    return (w.seconds - inside) / w.uploads * 1e3
