"""The one general generator of training traffic. A mix is a data file
``traffic/<mix>.json``; everything a cell's inputs depend on is the mix, the
configuration's sizes and ``--seed``. The mix names its generator by
``"kind"`` (``images`` where the key is absent); a batch is ``n`` ROWS.

``images`` are made as uint8 (what a decoded record is), smooth fields with
per-image brightness over pixel noise, so that items differ as photographs
do and not as white noise does; labels are uniform over the classes. A
``resident`` mix keeps a pool of ready float32 batches on the device and
cycles it: the feed does nothing. A ``fed`` mix keeps the pool on the HOST as
uint8 and serves it through the program's own ``io.DeviceFeedIter`` (uint8
on the wire, rescaled on the device), cycled for ever.

``tokens`` are rows of ``seq_len + 1`` ids cut from one stream of documents
packed back to back: lengths heavy-tailed (the mix's ``lengths``), every
document opened by the mix's ``boundary_id``, the other ids Zipf-distributed
over the ``vocab_held`` ids the configuration keeps, so that what looks at
token identity (an embedding's rows, a router) sees the uneven load text
gives it. ``x = row[:-1]``, ``labels = row[1:]``; no loss mask and no
attention mask at a boundary. Both are handed over in the mix's ``dtype``,
resident or through ``io.DeviceFeedIter`` with no wire type and no rescale.
"""
import math


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


def batch_tokens(mix, cfg, key, index, n):
    """Batch ``index`` of the seed's stream: (int32 ids (n, seq_len), int32
    next ids (n, seq_len)). Pure function of its arguments; jit it."""
    import jax
    import jax.numpy as jnp
    ln, vocab, seq = mix["lengths"], cfg["vocab_held"], cfg["seq_len"]
    if ln["dist"] != "lognormal":
        raise SystemExit("chipbench: unknown length distribution %r" % (ln["dist"],))
    total = n * (seq + 1)
    kd, ki = jax.random.split(jax.random.fold_in(key, index))
    f32 = jnp.float32
    # more documents than the shortest could fit; those that start past the
    # end of the stream are dropped
    docs = total // ln["min"] + 1
    length = jnp.exp(f32(math.log(ln["median"]))
                     + f32(ln["sigma"]) * jax.random.normal(kd, (docs,), f32))
    length = jnp.clip(jnp.round(length), ln["min"], ln["max"]).astype(jnp.int32)
    starts = jnp.cumsum(length) - length          # the first document opens row 0
    opens = jnp.zeros((total,), jnp.bool_).at[starts].set(True, mode="drop")
    # rank r has probability ~ (r + 1) ** -exponent; the boundary id is no rank
    weight = jnp.arange(1, vocab, dtype=f32) ** -f32(mix["zipf_exponent"])
    cdf = jnp.cumsum(weight) / jnp.sum(weight)
    rank = jnp.searchsorted(cdf, jax.random.uniform(ki, (total,), f32))
    rank = jnp.minimum(rank, vocab - 2).astype(jnp.int32)
    ids = jnp.where(opens, jnp.int32(mix["boundary_id"]),
                    rank + (rank >= mix["boundary_id"]))
    rows = ids.reshape(n, seq + 1)
    return rows[:, :-1], rows[:, 1:]


def _images_resident(mix, cfg, x, y):
    import jax.numpy as jnp
    x = x.astype(jnp.float32) * jnp.float32(mix["scale"])
    if cfg["layout"] != "NHWC":
        x = jnp.transpose(x, (0, 3, 1, 2))
    return x, y.astype(jnp.float32)


def _images_wire(mix, cfg, x, y):
    import jax.numpy as jnp
    if cfg["layout"] != "NHWC":
        x = jnp.transpose(x, (0, 3, 1, 2))
    return x, y.astype(jnp.float32)


def _images_reference(mix, cfg, x, y):
    return x.astype("float32") * mix["scale"], y


def _tokens_program(mix, cfg, x, y):
    return x.astype(mix["dtype"]), y.astype(mix["dtype"])


# kind -> (the seed's raw batch, as a resident pool holds it for the program,
# as a host pool puts it on the wire, as the plain reference's loss takes it)
KINDS = {
    "images": (batch_u8, _images_resident, _images_wire, _images_reference),
    "tokens": (batch_tokens, _tokens_program, _tokens_program,
               lambda mix, cfg, x, y: (x, y)),
}


def kind(mix):
    name = mix.get("kind", "images")
    if name not in KINDS:
        raise SystemExit("chipbench: unknown traffic kind %r; traffic.py has %s"
                         % (name, sorted(KINDS)))
    return KINDS[name]


def reference_batch(mix, cfg, key, index, n):
    """Batch ``index`` as the plain reference's ``loss_fn`` takes it."""
    raw, _resident, _wire, reference = kind(mix)
    return reference(mix, cfg, *raw(mix, cfg, key, index, n))


def make_pool(mix, cfg, seed, n_rows, sharding):
    """The mix's pool: [(x, labels)] as the program is handed them: device
    batches for a resident mix, host numpy for a fed one."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    key = seed_key(seed)
    fed = mix["placement"] == "host"
    raw, resident, wire, _reference = kind(mix)

    def one(key, index):      # the key an argument: a constant would be
        x, y = raw(mix, cfg, key, index, n_rows)         # a new program per seed
        return (wire if fed else resident)(mix, cfg, x, y)

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
                             wire_dtype=f.get("wire_dtype"),
                             scale=mix.get("scale", 1.0))

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
