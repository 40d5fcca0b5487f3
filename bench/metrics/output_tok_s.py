"""Output tokens delivered in the window over the window's length."""


def read(run):
    return run.client.tokens_in_window() / run.seconds
