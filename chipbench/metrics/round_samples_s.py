"""Samples per second of training rounds: batch x the rounds that
completed in the window, over the window.  Court, rollback and replay
run inside ``train_round``, so their time is counted."""


def read(w):
    return w.batch * w.rounds / w.seconds
