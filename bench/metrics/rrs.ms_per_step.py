"""Device time of the RRS wire's all_to_all per train step, averaged
over the chips: the ops of the ``jax.lax.all_to_all`` inside
``dist/robust_reduce.py``'s ``shard_map`` (base name ``all_to_all``;
the all-to-all ops that GSPMD inserts elsewhere are named
``all-to-all`` and are not counted)."""

KERNELS = ("all_to_all",)


def read(ctx):
    steps = ctx["rec"]["work"]["steps"]
    s = ctx["trace"]["kernels"]["all_to_all"]
    return 1e3 * s / steps if steps and s > 0 else None
