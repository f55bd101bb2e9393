"""DWFL core: the paper's contribution (channel, privacy, protocol) plus
the unified mixing-matrix exchange engine (repro.core.exchange)."""
from repro.core.channel import ChannelConfig, ChannelState  # noqa: F401
from repro.core.exchange import (  # noqa: F401
    ExchangeSpec, FlatSpec, MixPlan, flatten_worker_tree, make_flat_spec,
    mix_exchange, resolve_spec, worker_unravelers,
)
from repro.core.protocol import (  # noqa: F401
    ProtocolConfig, make_flat_train_step, make_dynamic_flat_train_step,
    make_eval_fn,
    init_worker_params, epsilon_report,
)
from repro.core.trajectory import (  # noqa: F401
    ChunkRunner, TrajCarry, auto_chunk, concat_chunks, make_round_body,
    plan_chunks, replicate_major, run_per_round,
)
