"""Executions inside the window that overflowed a static buffer and ran
again with larger ones (`capacity_retries` over the window, all
clients): 0 once capacity feedback's sizes are found again by every
statement.  The harness already holds a window to it (`failed`); here
it is a number of its own."""


def read(run):
    return run.window.get("programs", {}).get("capacity_retries")
