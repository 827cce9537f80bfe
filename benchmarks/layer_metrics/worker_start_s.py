"""From the call that asks for the chip holder (serve.run, JaxTrainer.fit)
to jax.devices() returning in it: cluster, worker, grant and libtpu."""


def read(ctx):
    return ctx.get("worker_start_s")
