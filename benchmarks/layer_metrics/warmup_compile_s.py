"""``LLMServer.stats()['warmup_s']`` (every serving program compiled or read
from the cache), or the seconds of the train step's ``lower().compile()``."""


def read(ctx):
    return ctx.get("warmup_compile_s")
