"""Output tokens the clients received inside the window, over its seconds."""


def read(ctx):
    if ctx["kind"] == "train":
        return None
    return ctx["window_tokens"] / ctx["seconds"]
