"""Images served per second of window (host clock): every image whose
dispatch ran inside the window, a batch that straddles the close counted by
the share of its claim-to-completion time that lies inside, so the rate has
no step of one batch."""


def read(run):
    return run.images_in_window() / (run.t1 - run.t0)
