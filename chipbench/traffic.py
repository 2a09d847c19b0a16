"""The one general generator of training traffic. A mix is a data file
``traffic/<mix>.json``; everything a cell's inputs depend on is the mix, the
configuration's sizes and ``--seed``.

Images are made as uint8 (what a decoded record is), smooth fields with
per-image brightness over pixel noise, so that items differ as photographs
do and not as white noise does; labels are uniform over the classes. A
``resident`` mix keeps a pool of ready float32 batches on the device and
cycles it: the feed does nothing. A ``fed`` mix keeps the pool on the HOST as
uint8 and serves it through the program's own ``io.DeviceFeedIter`` (uint8
on the wire, rescaled on the device), cycled for ever.
"""


def seed_key(seed):
    """A key from any whole number up to and past 2**31."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def batch_u8(mix, cfg, key, index, n):
    """Batch ``index`` of the seed's stream: (uint8 NHWC images, int32
    labels). Pure function of its arguments; jit it."""
    import jax
    import jax.numpy as jnp
    im, hw = mix["image"], cfg["image"]
    k = jax.random.fold_in(key, index)
    kc, kb, kn, kl = jax.random.split(k, 4)
    # float32 said aloud: the package turns x64 on, and float64 noise on a
    # TPU compiles for minutes and runs emulated
    f32 = jnp.float32
    coarse = jax.random.normal(kc, (n, im["coarse"], im["coarse"], 3), f32)
    field = jax.image.resize(coarse, (n, hw, hw, 3), "bilinear")
    x = (f32(im["mean"]) + f32(im["contrast"]) * field
         + f32(im["brightness"]) * jax.random.normal(kb, (n, 1, 1, 1), f32)
         + f32(im["noise"]) * jax.random.normal(kn, (n, hw, hw, 3), f32))
    x = jnp.clip(jnp.round(x), 0, 255).astype(jnp.uint8)
    labels = jax.random.randint(kl, (n,), 0, cfg["classes"], jnp.int32)
    return x, labels


def as_program_sees(mix, cfg, x_u8):
    """float32 in the configuration's layout, as the wire's rescale gives."""
    import jax.numpy as jnp
    x = x_u8.astype(jnp.float32) * jnp.float32(mix["scale"])
    return x if cfg["layout"] == "NHWC" else jnp.transpose(x, (0, 3, 1, 2))


def make_pool(mix, cfg, seed, n_items, sharding):
    """The mix's pool: [(x, labels)] — device float32 batches for a resident
    mix, host uint8 / float32 numpy for a fed one."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    key = seed_key(seed)
    fed = mix["placement"] == "host"

    def one(key, index):      # the key an argument: a constant would be
        x, y = batch_u8(mix, cfg, key, index, n_items)   # a new program per seed
        if fed:
            if cfg["layout"] != "NHWC":
                x = jnp.transpose(x, (0, 3, 1, 2))
            return x, y.astype(jnp.float32)
        return as_program_sees(mix, cfg, x), y.astype(jnp.float32)

    make = jax.jit(one, out_shardings=None if fed else (sharding, sharding))
    pool = [make(key, jnp.int32(i)) for i in range(mix["pool"])]
    if fed:
        pool = [(np.asarray(x), np.asarray(y)) for x, y in pool]
    return pool


class Resident:
    """Cycles a pool of device batches; ``next()`` costs nothing."""

    def __init__(self, pool):
        self._pool, self._i = pool, 0

    def next(self):
        b = self._pool[self._i % len(self._pool)]
        self._i += 1
        return b

    def close(self):
        self._pool = None


def fed_feed(mix, pool, sharding):
    """Host pool -> a small DataIter of the benchmark's own -> the program's
    DeviceFeedIter as the mix describes it."""
    from mxnet_tpu import io as mxio

    class HostPool(mxio.DataIter):
        def __init__(self):
            super().__init__(int(pool[0][0].shape[0]))
            self._i = 0

        def reset(self):
            self._i = 0

        def next(self):
            x, y = pool[self._i % len(pool)]
            self._i += 1
            return mxio.DataBatch(data=[x], label=[y], pad=0)

    f = mix["feed"]
    it = mxio.DeviceFeedIter(HostPool(), sharding=sharding, depth=f["depth"],
                             wire_dtype=f["wire_dtype"], scale=mix["scale"])

    class Feed:
        def next(self):
            b = it.next()
            return b.data[0], b.label[0]

        def close(self):
            it.close()

    return Feed()


def make_feed(mix, pool, sharding):
    if mix["placement"] == "host":
        return fed_feed(mix, pool, sharding)
    return Resident(pool)
