"""The shared constructor/knob surface of every streamed-capable
estimator (round 4): one definition of the cache + checkpoint knobs, so
adding or renaming a streaming knob is a one-site change instead of a
per-estimator copy-paste.

Estimators inherit this FIRST (``class KMeans(StreamingEstimatorMixin,
_KMeansParams, Estimator)``); the mixin's ``__init__`` stores the knobs
and chains ``super().__init__()`` into the params machinery. Estimators
with extra knobs (GBT's ``stream_reservoir_capacity``) override
``__init__`` and delegate here.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple


def peek_stream(batches) -> Tuple[Optional[Any], Any]:
    """Peek the first batch of a training stream without losing it.

    Returns ``(first_batch_or_None, stream_for_iterate)``. The online
    trainers peek to fix the carry's array shapes before the loop; HOW
    the peeked batch is re-presented depends on the stream kind:

    - a :class:`flinkml_tpu.data.Dataset` is restartable and
      cursor-tracked: it is peeked with a throwaway prefetch-free
      iterator and handed to :func:`~flinkml_tpu.iteration.iterate`
      WHOLE, so the runtime owns the skip/cursor machinery (chaining a
      consumed iterator would hide the Dataset and break cursor
      checkpoint/resume);
    - an :class:`flinkml_tpu.data.ElasticFeed` (world-parallel
      global-order feed) follows the Dataset contract — peeked with a
      throwaway iteration, handed to ``iterate`` whole so its GLOBAL
      cursor (and the elastic reshard on resume) belongs to the runtime;
    - a LIST of batches is peeked in place and handed to ``iterate`` AS
      the list — ``iterate`` re-iterates it from the start (its replay
      fast-forward handles positioning), which is also what lets the
      self-healing recovery loop re-open it after a rollback (a chained
      one-shot iterator could never be rewound). Lists only: the
      runtime's stream detection treats a tuple as a static pytree, so
      a tuple feed must keep the chained-iterator path;
    - any other iterable is peeked destructively and re-chained.
    """
    try:
        from flinkml_tpu.data import Dataset, ElasticFeed
    except ImportError:  # pragma: no cover — data subsystem always ships
        Dataset = ElasticFeed = None
    if Dataset is not None and isinstance(batches, (Dataset, ElasticFeed)):
        return batches.peek(), batches
    if isinstance(batches, list):
        if not batches:
            return None, iter(())
        return batches[0], batches
    import itertools

    it = iter(batches)
    try:
        first = next(it)
    except StopIteration:
        return None, iter(())
    return first, itertools.chain([first], it)


def feed_world_size(batches) -> int:
    """The world size the checkpoint rescale guard should pin for a
    training feed: a :class:`~flinkml_tpu.data.Dataset`'s shard count or
    an :class:`~flinkml_tpu.data.ElasticFeed`'s world (both expose
    ``num_shards``); 1 for plain iterables (a single-controller feed has
    no data-plane parallelism to guard). This is what lifts the online
    trainers' old ``world_size=1`` pin to mesh-aware resume: snapshots
    record the feed's TRUE world, and a manager with
    ``rescale="reshard"`` restores them at any other."""
    world = getattr(batches, "num_shards", None)
    try:
        return max(1, int(world)) if world is not None else 1
    except (TypeError, ValueError):
        return 1


class StreamingEstimatorMixin:
    """Cache + checkpoint knobs shared by every streamed-capable
    estimator; see ``docs/development/iteration.md`` ("Out-of-core
    training") for the capacity model and the checkpoint protocol."""

    #: Subclasses whose trainers thread a ShardingPlan set this True;
    #: everyone else gets a constructor-time refusal of the knob.
    _SHARDING_PLAN_AWARE = False

    #: Subclasses whose trainers thread a PrecisionPolicy (the FML6xx
    #: policy-gated mixed-precision path) set this True; everyone else
    #: gets a constructor-time refusal of the knob.
    _PRECISION_AWARE = False

    def __init__(
        self,
        mesh=None,
        cache_dir: Optional[str] = None,
        cache_memory_budget_bytes: Optional[int] = None,
        checkpoint_manager=None,
        checkpoint_interval: int = 0,
        resume: bool = False,
        sharding_plan=None,
        precision=None,
    ):
        super().__init__()
        self.mesh = mesh
        self.cache_dir = cache_dir
        self.cache_memory_budget_bytes = cache_memory_budget_bytes
        self.checkpoint_manager = checkpoint_manager
        self.checkpoint_interval = checkpoint_interval
        self.resume = resume
        if sharding_plan is not None and not type(self)._SHARDING_PLAN_AWARE:
            # Constructor-time loud refusal: a silently-ignored plan on
            # a plan-unaware estimator would train replicated — exactly
            # the OOM the user configured the plan to avoid.
            raise ValueError(
                f"{type(self).__name__} does not support sharding_plan "
                "yet (plan-aware estimators: the linear family's dense "
                "paths — LogisticRegression, LinearSVC, LinearRegression)"
            )
        if precision is not None and not type(self)._PRECISION_AWARE:
            # Same loud-refusal contract as the plan knob: a silently
            # ignored policy would "train in bf16" at full f32 cost —
            # the measurement the policy was declared to change.
            raise ValueError(
                f"{type(self).__name__} does not support precision yet "
                "(policy-aware estimators: the linear family's dense "
                "paths — LogisticRegression, LinearSVC, LinearRegression — "
                "and the perceptrons' table fit)"
            )
        from flinkml_tpu.precision import resolve_policy

        #: Optional :class:`~flinkml_tpu.precision.PrecisionPolicy` (or
        #: preset name / JSON dict, resolved here so a bad spelling
        #: fails at construction) — policy-aware estimators validate
        #: their step's jaxpr against it BEFORE any compile (FML6xx)
        #: and run compute at ``policy.compute``; see
        #: ``docs/development/precision.md``.
        self.precision = resolve_policy(precision)
        #: Optional :class:`~flinkml_tpu.sharding.plan.ShardingPlan` —
        #: plan-aware estimators (``_SHARDING_PLAN_AWARE = True``; the
        #: linear family's dense paths) shard parameters + optimizer
        #: state per the plan; every other estimator refuses the knob at
        #: construction, and the aware ones refuse it loudly on their
        #: plan-unaware branches (sparse features, streamed fits).
        self.sharding_plan = sharding_plan

    def _checkpoint_kwargs(self) -> dict:
        return dict(
            checkpoint_manager=self.checkpoint_manager,
            checkpoint_interval=self.checkpoint_interval,
            resume=self.resume,
        )

    def _reject_in_ram_checkpointing(self, detail: str = "") -> None:
        """In-RAM fits that cannot checkpoint raise loudly instead of
        silently dropping the knobs."""
        if self.checkpoint_manager is not None or self.resume:
            raise ValueError(
                "checkpointing is supported for streamed fits only "
                "(pass an iterable of batch Tables or a DataCache)"
                + (f"; {detail}" if detail else "")
            )
