"""Keys whose result was ready by the end of the window, over the window's
seconds; the window ends when the last dispatched call is ready."""


def read(run):
    keys = run.window.get("keys")
    return keys / run.window["seconds"] if keys else None
