"""``peak_bytes_in_use`` on the fullest chip at the end of set-up, when
the program has run once at the cell's shapes and the window has not
yet kept any output for its check."""


def read(run):
    if not run.setup_peak_bytes:
        return None
    return run.setup_peak_bytes / 1e9
