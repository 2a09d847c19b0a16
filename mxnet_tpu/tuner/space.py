"""Search-space declaration for the cost-model-guided autotuner.

A :class:`Candidate` is one fully-specified training-step configuration —
the five perf levers the staged bench ladders have been exercising by hand
(ROADMAP item 1): global **batch** size, conv **layout** (NCHW/NHWC),
**remat** policy, buffer **donation** and device-feed **prefetch depth**,
plus the gradient-reduction levers. A :class:`SearchSpace` is the declared
cross product the tuner enumerates; invalid combinations (``bucket_bytes``
next to ``reduce_scatter``) are skipped at enumeration, never at build
time.

Candidates are *data*: they serialize to/from plain dicts (the
``tuner_config`` field of a cost-ledger trial row), produce a stable
``key()`` for warm-start cache lookups, and apply themselves to a live
:class:`~mxnet_tpu.parallel.DataParallelTrainer` via ``build_trainer`` /
``trainer_kwargs`` — the round trip the acceptance test pins bitwise at
the HLO level.
"""
from __future__ import annotations

import itertools
import json
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from ..base import MXNetError

__all__ = ["LAYOUTS", "REMAT_MODES", "GRAD_REDUCE_MODES", "Candidate",
           "SearchSpace"]

LAYOUTS = ("NCHW", "NHWC")
# the remat spellings DataParallelTrainer knows (None == "none" == off);
# callables are deliberately out of the search space — they don't serialize
REMAT_MODES = (None, "none", "full", "dots")
# the gradient-reduction strategies DataParallelTrainer knows: plain
# replicated all-reduce vs the ZeRO-1 reduce-scatter + sharded optimizer
GRAD_REDUCE_MODES = ("all_reduce", "reduce_scatter")


def _graph_rules(input_layout: Optional[str] = None):
    """The trainer's default pipeline, pinned: with ``layout`` where an
    NCHW-built net is to run channel-last (``input_layout``), else without
    it.  The rules that do not depend on the layout (``fold``; ``fusion``,
    which sinks a stem's max pool in front of its BatchNorm) are part of
    every default trainer's step, so the flags route, the passes route and
    the NCHW baseline all measure that step and differ in layout alone."""
    from ..passes import DEFAULT_PIPELINE, PassManager
    if input_layout:
        return PassManager(DEFAULT_PIPELINE, input_layout=input_layout)
    return PassManager([p for p in DEFAULT_PIPELINE if p != "layout"])


def _norm_remat(remat) -> Optional[str]:
    if remat in (None, "none"):
        return None
    if remat in ("full", "dots"):
        return str(remat)
    raise MXNetError(f"candidate remat must be one of {REMAT_MODES}, "
                     f"got {remat!r}")


def _norm_reduce_dtype(dt) -> Optional[str]:
    if dt in (None, "none", "", "float32", "f32"):
        return None
    alias = {"bf16": "bfloat16", "fp16": "float16"}
    dt = alias.get(str(dt), str(dt))
    if dt not in ("bfloat16", "float16"):
        raise MXNetError("candidate grad_reduce_dtype must be none/"
                         f"bfloat16/float16, got {dt!r}")
    return dt


class Candidate:
    """One point of the search space. Immutable value object."""

    __slots__ = ("batch", "layout", "remat", "donate",
                 "prefetch_depth", "grad_reduce", "grad_reduce_dtype",
                 "bucket_bytes")

    def __init__(self, batch: int, layout: str = "NCHW",
                 remat=None, donate: bool = True, prefetch_depth: int = 2,
                 grad_reduce: str = "all_reduce", grad_reduce_dtype=None,
                 bucket_bytes: Optional[int] = None):
        batch = int(batch)
        if batch <= 0:
            raise MXNetError(f"candidate batch must be positive, got {batch}")
        if layout not in LAYOUTS:
            raise MXNetError(f"candidate layout must be one of {LAYOUTS}, "
                             f"got {layout!r}")
        if grad_reduce not in GRAD_REDUCE_MODES:
            raise MXNetError("candidate grad_reduce must be one of "
                             f"{GRAD_REDUCE_MODES}, got {grad_reduce!r}")
        if bucket_bytes in (None, 0, "none"):
            bucket_bytes = None
        else:
            bucket_bytes = int(bucket_bytes)
            if bucket_bytes <= 0:
                raise MXNetError("candidate bucket_bytes must be positive, "
                                 f"got {bucket_bytes}")
            if grad_reduce == "reduce_scatter":
                raise MXNetError(
                    "bucket_bytes is an all_reduce-path lever; the ZeRO "
                    "reduce_scatter path fuses its own per-leaf collectives "
                    "(DataParallelTrainer enforces the same)")
        object.__setattr__(self, "batch", batch)
        object.__setattr__(self, "layout", str(layout))
        object.__setattr__(self, "remat", _norm_remat(remat))
        object.__setattr__(self, "donate", bool(donate))
        object.__setattr__(self, "prefetch_depth", max(0, int(prefetch_depth)))
        object.__setattr__(self, "grad_reduce", str(grad_reduce))
        object.__setattr__(self, "grad_reduce_dtype",
                           _norm_reduce_dtype(grad_reduce_dtype))
        object.__setattr__(self, "bucket_bytes", bucket_bytes)

    def __setattr__(self, *_):
        raise AttributeError("Candidate is immutable")

    # ------------------------------------------------------------- identity
    @property
    def label(self) -> str:
        """Human-readable tag, perf_lab-style core (``NHWC:512``) plus any
        non-default lever suffixes."""
        tag = f"{self.layout}:{self.batch}"
        if self.remat:
            tag += f"+remat={self.remat}"
        if not self.donate:
            tag += "+nodonate"
        if self.prefetch_depth != 2:
            tag += f"+pf{self.prefetch_depth}"
        if self.grad_reduce != "all_reduce":
            tag += "+rs"
        if self.grad_reduce_dtype is not None:
            tag += f"+rd={self.grad_reduce_dtype}"
        if self.bucket_bytes is not None:
            tag += f"+bb={self.bucket_bytes}"
        return tag

    def as_dict(self) -> Dict[str, Any]:
        return {"batch": self.batch, "layout": self.layout,
                "remat": self.remat, "donate": self.donate,
                "prefetch_depth": self.prefetch_depth,
                "grad_reduce": self.grad_reduce,
                "grad_reduce_dtype": self.grad_reduce_dtype,
                "bucket_bytes": self.bucket_bytes}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Candidate":
        return cls(**{k: d[k] for k in cls.__slots__ if k in d})

    def key(self, device_kind: Optional[str] = None, model: str = "",
            n_devices: int = 1, compute_dtype=None,
            optimizer=None, data_shapes=None, feed: bool = False) -> str:
        """Stable warm-start cache key: the full config plus EVERYTHING
        else that changes the executable or the wall clock it was measured
        on — device kind, chip count, model signature, compute dtype,
        optimizer and the sample batch's shape/dtype signature (the
        ``data()`` callback controls image size/classes beyond
        batch/layout). A hit must mean "this exact program on this exact
        topology was scored before"; omitting any of these would let a
        search silently reuse measurements of a program or hardware that
        was never run."""
        doc = dict(self.as_dict())
        doc["device_kind"] = device_kind
        doc["n_devices"] = int(n_devices)
        doc["model"] = model or ""
        doc["compute_dtype"] = str(compute_dtype) if compute_dtype else None
        doc["optimizer"] = repr(optimizer) if optimizer else None
        doc["data_shapes"] = data_shapes
        # feed-measured wall clocks (prefetch pipeline) are not comparable
        # to device-resident ones — they must never warm-start each other
        doc["feed"] = bool(feed)
        return json.dumps(doc, sort_keys=True)

    def __eq__(self, other) -> bool:
        return isinstance(other, Candidate) and \
            self.as_dict() == other.as_dict()

    def __hash__(self) -> int:
        return hash(tuple(sorted(self.as_dict().items())))

    def __repr__(self) -> str:
        return f"Candidate({self.label})"

    # ------------------------------------------------------------ appliers
    def data_shape(self, image: int = 224,
                   channels: int = 3) -> Tuple[int, ...]:
        """The input-batch shape this candidate trains on (conv nets)."""
        if self.layout == "NHWC":
            return (self.batch, image, image, channels)
        return (self.batch, channels, image, image)

    def trainer_kwargs(self) -> Dict[str, Any]:
        """The DataParallelTrainer ctor levers this candidate carries.
        ``batch``/``layout`` are data- and net-level choices (the
        caller's ``build``/``data`` functions consume them); ``prefetch_depth``
        is a feed-level knob (``io.prefetch_to_device(depth=...)``). The
        comm levers (``grad_reduce``/``grad_reduce_dtype``/``bucket_bytes``)
        pass straight through — ``mxtune`` searches comm config exactly
        like it searches layout/remat."""
        return {"remat": self.remat, "donate": self.donate,
                "grad_reduce": self.grad_reduce,
                "grad_reduce_dtype": self.grad_reduce_dtype,
                "bucket_bytes": self.bucket_bytes}

    def passes_manager(self):
        """This candidate's ``layout`` dimension as a graph-pass
        pipeline over an NCHW-built net (``mxnet_tpu.passes``): the
        flag-vs-pass route.  ``input_layout="NHWC"`` because the
        candidate's ``data_shape`` feeds channel-last batches; the
        rewritten step is bitwise-HLO-identical to the hand-flagged net
        under :func:`_graph_rules` (the tuner round-trip acceptance test).
        An NCHW candidate has no layout to rewrite and takes those rules
        alone, as the flags route does."""
        if self.layout != "NHWC":
            return _graph_rules()
        return _graph_rules(input_layout="NHWC")

    def build_trainer(self, net, loss_fn, optimizer: str = "sgd",
                      optimizer_params: Optional[Dict] = None,
                      via_passes: bool = False, **extra):
        """Apply this candidate to a trainer: the returned
        ``DataParallelTrainer`` is EXACTLY the one a hand-written
        ``DataParallelTrainer(net, loss, ..., remat=..., donate=...)`` would
        build (bitwise-identical lowered HLO — the tuner acceptance test).

        ``via_passes=True`` applies the layout dimension as graph
        passes instead of expecting a hand-flagged net: ``net`` must be
        built NCHW, and the candidate's pipeline rewrites the captured
        graph to the identical HLO.  Either way the candidate PINS its
        pass configuration explicitly (the flags route runs
        :func:`_graph_rules`, the default pipeline less ``layout``) — a
        tuner trial must measure exactly its declared config, never the
        ambient ``MXNET_PASSES``."""
        from ..parallel import DataParallelTrainer
        kw = self.trainer_kwargs()
        kw["passes"] = self.passes_manager() if via_passes \
            else _graph_rules()
        kw.update(extra)
        return DataParallelTrainer(net, loss_fn, optimizer,
                                   optimizer_params or {}, **kw)


class SearchSpace:
    """Declared cross product of lever values.

    Dimension order is significant: :meth:`enumerate` varies the LAST
    dimension fastest, so the first emitted candidate is the first value of
    every dimension — the space's **baseline** the CLI measures improvement
    against.
    """

    DIMS = ("batch", "layout", "remat", "donate", "prefetch_depth",
            "grad_reduce", "grad_reduce_dtype", "bucket_bytes")

    def __init__(self, batch: Sequence[int] = (256, 512),
                 layout: Sequence[str] = ("NCHW", "NHWC"),
                 remat: Sequence = (None,),
                 donate: Sequence[bool] = (True,),
                 prefetch_depth: Sequence[int] = (2,),
                 grad_reduce: Sequence[str] = ("all_reduce",),
                 grad_reduce_dtype: Sequence = (None,),
                 bucket_bytes: Sequence = (None,)):
        def tup(v):
            return tuple(v) if isinstance(v, (list, tuple)) else (v,)
        self.batch = tup(batch)
        self.layout = tup(layout)
        self.remat = tup(remat)
        self.donate = tup(donate)
        self.prefetch_depth = tup(prefetch_depth)
        self.grad_reduce = tup(grad_reduce)
        self.grad_reduce_dtype = tup(grad_reduce_dtype)
        self.bucket_bytes = tup(bucket_bytes)
        for name in self.DIMS:
            if not getattr(self, name):
                raise MXNetError(f"search-space dimension {name!r} is empty")

    def enumerate(self) -> List[Candidate]:
        """Every valid candidate, baseline first. Invalid combinations
        (bucket_bytes next to the ZeRO reduce_scatter path, which fuses
        its own collectives) are skipped,
        not errors — a space may legitimately declare both values of every
        dimension at once."""
        out: List[Candidate] = []
        for vals in itertools.product(self.batch, self.layout,
                                      self.remat, self.donate,
                                      self.prefetch_depth, self.grad_reduce,
                                      self.grad_reduce_dtype,
                                      self.bucket_bytes):
            b, lay, rm, don, pf, gr, grd, bb = vals
            if bb not in (None, 0) and gr == "reduce_scatter":
                continue
            out.append(Candidate(b, lay, remat=rm, donate=don,
                                 prefetch_depth=pf, grad_reduce=gr,
                                 grad_reduce_dtype=grd, bucket_bytes=bb))
        if not out:
            raise MXNetError("search space enumerates to zero valid "
                             "candidates")
        return out

    def baseline(self) -> Candidate:
        """First valid candidate — what a user who sets no levers runs."""
        return self.enumerate()[0]

    def __len__(self) -> int:
        return len(self.enumerate())

    def as_dict(self) -> Dict[str, Any]:
        return {k: list(getattr(self, k)) for k in self.DIMS}

    def __repr__(self) -> str:
        return f"SearchSpace({self.as_dict()})"

    # --------------------------------------------------------------- parse
    _ALIASES = {"prefetch": "prefetch_depth", "pf": "prefetch_depth",
                "reduce": "grad_reduce", "reduce_dtype": "grad_reduce_dtype",
                "bucket": "bucket_bytes"}

    @classmethod
    def from_spec(cls, spec: str) -> "SearchSpace":
        """Parse the CLI spelling: ``dim=v1,v2;dim=v1`` — e.g.
        ``batch=256,512;layout=NHWC;remat=none,full;donate=1,0;``
        ``grad_reduce=all_reduce,reduce_scatter;grad_reduce_dtype=none,bf16;``
        ``bucket_bytes=none,4194304``."""
        kw: Dict[str, Any] = {}
        for part in (spec or "").split(";"):
            part = part.strip()
            if not part:
                continue
            if "=" not in part:
                raise MXNetError(f"bad search-space token {part!r} "
                                 "(want dim=v1,v2)")
            name, _, vals = part.partition("=")
            name = cls._ALIASES.get(name.strip(), name.strip())
            if name not in cls.DIMS:
                raise MXNetError(f"unknown search-space dimension {name!r} "
                                 f"(known: {', '.join(cls.DIMS)})")
            parsed: List[Any] = []
            for tok in vals.split(","):
                tok = tok.strip()
                if name == "batch" or name == "prefetch_depth":
                    parsed.append(int(tok))
                elif name == "donate":
                    parsed.append(tok.lower() in ("1", "true", "yes", "on"))
                elif name in ("remat", "grad_reduce_dtype"):
                    parsed.append(None if tok.lower() in ("none", "off", "")
                                  else tok)
                elif name == "bucket_bytes":
                    parsed.append(None if tok.lower() in ("none", "off", "0",
                                                          "")
                                  else int(tok))
                else:
                    parsed.append(tok)
            kw[name] = tuple(parsed)
        if "batch" not in kw:
            raise MXNetError("search space needs at least batch=...")
        return cls(**kw)
