"""
Static model specifications — the TPU-first replacement for "a compiled Keras
model object".

Where the reference's factories return a live ``keras.Sequential``
(gordo/machine/model/factories/*.py), gordo-tpu factories return a frozen
**ModelSpec**. The spec is:

- *static*: pure data (tuples, floats, strings) → safely closed over by
  ``jit``; no retracing surprises;
- *hashable*: the fleet trainer groups thousands of machines by spec so each
  distinct architecture compiles exactly once (SURVEY.md §7 step 7,
  "compilation buckets");
- *declarative*: the training engine (models/training.py) turns a spec into
  init/forward/loss functions.
"""

from dataclasses import dataclass, field, fields
from typing import Any, Dict, Optional, Tuple, Union


def _freeze_kwargs(kwargs: Optional[Dict[str, Any]]) -> Tuple[Tuple[str, Any], ...]:
    if not kwargs:
        return ()
    return tuple(sorted(kwargs.items()))


@dataclass(frozen=True)
class OptimizerSpec:
    """
    Optimizer configuration. Defaults mirror Keras' Adam
    (learning_rate=1e-3, beta_1=0.9, beta_2=0.999, epsilon=1e-7) so that
    configs written for the reference train equivalently.
    """

    name: str = "Adam"
    learning_rate: float = 0.001
    kwargs: Tuple[Tuple[str, Any], ...] = ()

    @classmethod
    def from_config(
        cls,
        optimizer: Union[str, "OptimizerSpec", None] = "Adam",
        optimizer_kwargs: Optional[Dict[str, Any]] = None,
    ) -> "OptimizerSpec":
        if isinstance(optimizer, OptimizerSpec):
            return optimizer
        optimizer_kwargs = dict(optimizer_kwargs or {})
        lr = optimizer_kwargs.pop(
            "learning_rate", optimizer_kwargs.pop("lr", 0.001)
        )
        return cls(
            name=optimizer or "Adam",
            learning_rate=float(lr),
            kwargs=_freeze_kwargs(optimizer_kwargs),
        )

    def to_optax(self):
        import optax

        kwargs = dict(self.kwargs)
        name = self.name.lower()
        if name == "adam":
            return optax.adam(
                learning_rate=self.learning_rate,
                b1=kwargs.get("beta_1", 0.9),
                b2=kwargs.get("beta_2", 0.999),
                eps=kwargs.get("epsilon", 1e-7),
            )
        if name == "adamw":
            return optax.adamw(
                learning_rate=self.learning_rate,
                b1=kwargs.get("beta_1", 0.9),
                b2=kwargs.get("beta_2", 0.999),
                eps=kwargs.get("epsilon", 1e-7),
                weight_decay=kwargs.get("weight_decay", 1e-4),
            )
        if name == "sgd":
            return optax.sgd(
                learning_rate=self.learning_rate,
                momentum=kwargs.get("momentum", 0.0),
                nesterov=kwargs.get("nesterov", False),
            )
        if name == "rmsprop":
            return optax.rmsprop(
                learning_rate=self.learning_rate,
                decay=kwargs.get("rho", 0.9),
                eps=kwargs.get("epsilon", 1e-7),
                momentum=kwargs.get("momentum", 0.0),
            )
        raise ValueError(f"Unsupported optimizer {self.name!r}")


class ModelSpec:
    """Base of the architecture specs; concrete specs are frozen
    dataclasses. A spec answers for itself: which functions initialise
    and run it, how many parameters it trains and what a sample costs.
    Consumers ask the spec (``nn.init_fn_for``, ``nn.forward_fn_for``,
    the planner's counts) instead of choosing by type."""

    #: True for many-to-one models over a ``lookback_window`` of rows:
    #: they train and score through the on-device windowing programs
    windowed = False

    #: False for a spec whose forward cannot run under ``vmap`` over a
    #: member axis on every backend: its members train and score one to
    #: a program (``planner.packing.trains_alone``)
    member_axis = True

    def init_fn(self):
        """``(rng, spec) -> params``."""
        raise TypeError(f"No init function for spec type {type(self).__name__}")

    def forward_fn(self):
        """``(spec, params, x) -> (output, penalty)``."""
        raise TypeError(f"No forward function for spec type {type(self).__name__}")

    def forward_aux_fn(self):
        """``(spec, params, x, active=None) -> (output, penalty, aux)``
        where the forward has counters of its own to give (a dict of
        arrays that a fit program sums over its steps, beside
        ``steps_run``, the steps that held data), else None. ``active
        [batch]`` marks the samples that count in the caller's loss: a
        fit step's padding is no work of the model's to count, or to
        do where it can be left out."""
        return None

    def fit_counter_attrs(self, counters: Dict[str, Any]) -> Dict[str, Any]:
        """What a fit program's ``device_program`` span and
        ``build_status.json["fit_counters"]`` say of ``counters`` (the
        forward's own, summed over a fit's members, epochs and steps):
        each as a list, and whatever of the spec a reader needs to read
        them."""
        return {name: value.tolist() for name, value in counters.items()}

    def program_attrs(self) -> Dict[str, Any]:
        """What every device program of the spec, fit or predict, says of
        itself on its ``device_program`` span: numbers of the program
        that its shapes decide."""
        return {}

    def param_count(self) -> int:
        """Trainable parameters from the geometry alone; 0 = unknown
        (the planner then keeps the member in a group of its own)."""
        return 0

    def flops_per_sample(self) -> float:
        """Forward FLOPs of one sample (a row, or a window); about 2 a
        parameter a sample is the dense-layer identity and the fallback."""
        return 2.0 * self.param_count()

    def to_dict(self) -> dict:
        out: Dict[str, Any] = {"spec_type": type(self).__name__}
        for f in fields(self):  # type: ignore[arg-type]
            value = getattr(self, f.name)
            if isinstance(value, OptimizerSpec):
                value = {
                    "name": value.name,
                    "learning_rate": value.learning_rate,
                    **dict(value.kwargs),
                }
            out[f.name] = value
        return out


@dataclass(frozen=True)
class FeedForwardSpec(ModelSpec):
    """
    A feedforward (dense) autoencoder/regressor: ``dims[i]`` hidden units
    with ``activations[i]``, then an output layer of ``n_features_out`` with
    ``out_activation``. ``l1_activity[i]`` adds an L1 activity penalty on
    layer ``i``'s output to the loss (the reference puts l1(1e-4) on all
    non-first encoder layers — factories/feedforward_autoencoder.py:75-84).
    """

    n_features: int
    n_features_out: int
    dims: Tuple[int, ...]
    activations: Tuple[str, ...]
    out_activation: str = "linear"
    l1_activity: Tuple[float, ...] = ()
    optimizer: OptimizerSpec = field(default_factory=OptimizerSpec)
    loss: str = "mse"
    compute_dtype: str = "float32"
    #: serving precision from the config surface ("" inherits the
    #: GORDO_TPU_SERVE_PRECISION knob): "f32", "bf16" or "int8" — read
    #: only by the serve engine's precision ladder, never by training.
    #: A plain class-level default keeps pre-precision pickled specs
    #: loading (attribute access falls back to the class default).
    precision: str = ""

    def __post_init__(self):
        if len(self.dims) != len(self.activations):
            raise ValueError(
                f"dims ({len(self.dims)}) and activations "
                f"({len(self.activations)}) must have equal length"
            )
        if self.l1_activity and len(self.l1_activity) != len(self.dims):
            raise ValueError("l1_activity must match dims length when given")

    def init_fn(self):
        from .nn import init_feedforward

        return init_feedforward

    def forward_fn(self):
        from .nn import forward_feedforward

        return forward_feedforward

    def _widths(self) -> Tuple[int, ...]:
        return (self.n_features,) + tuple(self.dims) + (self.n_features_out,)

    def param_count(self) -> int:
        widths = self._widths()
        return sum(d_in * d_out + d_out for d_in, d_out in zip(widths, widths[1:]))

    def flops_per_sample(self) -> float:
        widths = self._widths()
        return float(sum(2 * d_in * d_out for d_in, d_out in zip(widths, widths[1:])))


@dataclass(frozen=True)
class LSTMSpec(ModelSpec):
    """
    A stacked LSTM many-to-one network over a ``lookback_window`` of
    timesteps: every LSTM layer returns sequences except the last, followed
    by a Dense output head (reference architecture:
    factories/lstm_autoencoder.py:78-97).
    """

    n_features: int
    n_features_out: int
    lookback_window: int
    dims: Tuple[int, ...]
    activations: Tuple[str, ...]
    out_activation: str = "linear"
    optimizer: OptimizerSpec = field(default_factory=OptimizerSpec)
    loss: str = "mse"
    compute_dtype: str = "float32"
    #: serving precision from the config surface (see FeedForwardSpec;
    #: LSTMs serve unbatched today, so this is carried, not yet used)
    precision: str = ""

    windowed = True

    def __post_init__(self):
        if len(self.dims) != len(self.activations):
            raise ValueError(
                f"dims ({len(self.dims)}) and activations "
                f"({len(self.activations)}) must have equal length"
            )
        if not self.dims:
            raise ValueError("LSTM spec needs at least one layer")

    def init_fn(self):
        from .nn import init_lstm

        return init_lstm

    def forward_fn(self):
        from .nn import forward_lstm

        return forward_lstm

    def param_count(self) -> int:
        total, d_in = 0, self.n_features
        for d_h in self.dims:
            # 4 gates, each [d_in + d_h, d_h] + bias
            total += 4 * (d_in * d_h + d_h * d_h + d_h)
            d_in = d_h
        return total + d_in * self.n_features_out + self.n_features_out

    def flops_per_sample(self) -> float:
        """One window: the recurrence runs ``lookback_window`` steps."""
        per_step, d_in = 0.0, self.n_features
        for d_h in self.dims:
            per_step += 2.0 * 4 * (d_in + d_h) * d_h
            d_in = d_h
        return per_step * self.lookback_window + 2.0 * d_in * self.n_features_out


#: layer kinds of a :class:`BackboneSpec`
OPERATORS = (
    "conv", "full_attention", "sparse_attention", "sliding_attention",
    "mamba", "gmu", "cross_attention",
)
#: the operators that project to query heads (and, all but
#: ``cross_attention``, to key and value heads)
ATTENTIONS = ("full_attention", "sparse_attention", "sliding_attention", "cross_attention")
#: what an operator reads of an earlier layer beside the residual, and
#: the operator that makes it: a ``gmu`` the scan output of the last
#: ``mamba`` before it, a ``cross_attention`` the keys and values of the
#: last ``full_attention`` before it
READS = {"gmu": "mamba", "cross_attention": "full_attention"}
FFNS = ("dense", "moe")
#: how a routed layer scores its experts: ``sigmoid_bias`` (sigmoid
#: scores, the ``k`` largest ``score + bias`` chosen, a bias buffer),
#: ``softmax`` (softmax over all logits, the ``k`` largest renormalised
#: to sum 1, no bias) or ``softmax_of_chosen`` (the ``k`` largest logits
#: chosen, a softmax over those: the same weights, and the same choice
#: wherever the softmax over all does not round two experts to one
#: value; where logits lie some 90 apart it rounds all but the largest
#: to zero, and its ``k`` largest are then the largest and whatever a
#: tie picks)
ROUTERS = ("sigmoid_bias", "softmax", "softmax_of_chosen")
#: the tensor a routed layer's router reads: ``ffn_input`` (the normed
#: tensor its experts read, after the operator) or ``layer_input`` (the
#: layer's input, before the operator and before its norm: the routing
#: then depends on nothing the operator computes)
ROUTER_INPUTS = ("ffn_input", "layer_input")
#: the gate of an expert: ``act(x W_1) * (x W_3)``
EXPERT_ACTIVATIONS = ("silu", "relu")
#: a rotary embedding's ``rope_type``; ``none``: no position encoding
ROPE_TYPES = ("default", "yarn", "none")
#: the norm of a block's two inputs and of the head's: ``rms`` (a gain)
#: or ``layer`` (``nn.LayerNorm``: mean and variance, a gain and a bias)
NORMS = ("rms", "layer")


@dataclass(frozen=True)
class BackboneSpec(ModelSpec):
    """
    A language-model backbone as a many-to-one sensor model (ROADMAP,
    Reach): a linear projection from ``n_features`` to ``hidden_size``
    stands where the token embedding stood, ``layer_ops[i]`` /
    ``layer_ffns[i]`` blocks follow at published widths (pre-norm
    residual blocks, RMSNorm, no bias), and the final norm and a linear
    head to ``n_features_out``, read at the window's last position,
    stand where the LM head stood. ``models/backbone.py`` has the layer
    equations.

    The routed expert layer is a *share* layer: the router scores all
    ``num_experts`` published experts and keeps ``num_experts_per_tok``
    of them; this holder computes the part of the result that experts
    ``expert_offset .. expert_offset + experts_held - 1`` give. With
    ``experts_held == num_experts`` that is the whole layer.

    ``sparse_attention`` is grouped-query attention over the keys a
    learned indexer selects (DeepSeek-Sparse-Attention): ``index_n_heads``
    heads of ``index_head_dim`` score every causal key, a query attends
    to its ``index_topk`` best, computed in square tiles of
    ``index_chunk`` queries by ``index_chunk`` keys. The indexer
    learns from its own objective, the ``penalty`` of the forward.

    ``sliding_attention`` is grouped-query attention limited by
    distance: a query sees itself and the ``sliding_window - 1`` rows
    before it. It and a ``full_attention`` layer over a window longer
    than a tile (``backbone.ATTENTION_TILE`` rows) are computed in
    square tiles, a block of queries against the tiles its mask
    reaches. ``layer_heads`` gives each layer its own number of
    query heads (empty: ``num_attention_heads`` in every layer),
    ``rope_parameters`` each operator its own rotary embedding (an
    operator it does not name: ``rope_theta`` over the whole head;
    ``rope_type: none``: that operator's ``q`` and ``k`` carry no
    position at all),
    ``attention_gate`` a sigmoid gate on each head's output,
    ``shared_expert_intermediate_size`` an expert that every token
    takes beside the routed ones (0: none). ``router_input`` says which
    tensor a routed layer's router reads (``ROUTER_INPUTS``) and
    ``expert_activation`` the gate of its experts
    (``EXPERT_ACTIVATIONS``).

    ``kv_lora_rank`` above 0 makes every attention a latent one
    (``deepseek_v3``'s): keys and values come from one projection to a
    latent of that width, normed, and one expansion to ``qk_nope_head_dim``
    of each head's key and ``v_head_dim`` of its value; the other
    ``qk_rope_head_dim`` of a key are one rotary key that every head
    shares, read beside the latent and not normed. A head's scores are
    ``head_dim = qk_nope_head_dim + qk_rope_head_dim`` wide and its values
    ``v_head_dim``; the rotary embedding turns the trailing
    ``qk_rope_head_dim`` of ``q`` and the shared key alone, in
    interleaved pairs under ``rope_interleave``.

    ``mamba`` is a selective state-space layer (Mamba-1's): a causal
    depthwise convolution of ``ssm_conv`` taps with a bias and a
    ``silu`` over an inner stream ``ssm_inner`` wide, then a scan over
    the window's rows of a float32 state of ``ssm_state`` numbers a
    channel whose step, input and output matrices each row chooses (the
    step through a projection of rank ``ssm_dt_rank``), gated by the
    other half of the input projection. ``gmu`` (a gated memory unit)
    gates the scan output of the last ``mamba`` before it, taken before
    that layer's own gate, with a projection of its own input;
    ``cross_attention`` projects queries alone and attends to the keys
    and values of the last ``full_attention`` before it (``READS``;
    :attr:`layer_sources`). Under ``differential`` every attention's
    heads pair up, neighbours together: two softmax maps over one value
    twice a head wide, their difference under a learned weight, an
    RMSNorm over it. ``attention_bias`` puts a bias on an attention's
    projections; ``norm: layer`` makes the blocks' norms and the head's
    LayerNorms with a bias.
    """

    n_features: int
    n_features_out: int
    lookback_window: int
    layer_ops: Tuple[str, ...]
    layer_ffns: Tuple[str, ...]
    hidden_size: int = 2048
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_experts: int = 32
    experts_held: int = 32
    expert_offset: int = 0
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    optimizer: OptimizerSpec = field(default_factory=OptimizerSpec)
    loss: str = "mse"
    compute_dtype: str = "float32"
    precision: str = ""
    #: width of an attention head where the config states it; 0: the
    #: family's ``hidden_size // num_attention_heads``
    attention_head_dim: int = 0
    router: str = "sigmoid_bias"
    index_n_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    index_chunk: int = 512
    layer_heads: Tuple[int, ...] = ()
    #: ``((operator, ((key, value), ...)), ...)``: HF ``rope_parameters``
    #: by layer type, as plain sorted pairs (:meth:`rope_of` reads them)
    rope_parameters: Tuple[Tuple[str, Tuple[Tuple[str, Any], ...]], ...] = ()
    qk_norm: bool = True
    attention_gate: bool = False
    sliding_window: int = 0
    shared_expert_intermediate_size: int = 0
    router_input: str = "ffn_input"
    expert_activation: str = "silu"
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False
    norm: str = "rms"
    attention_bias: bool = False
    differential: bool = False
    ssm_inner: int = 0
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_dt_rank: int = 0

    windowed = True
    #: a backbone's member is a program of its own, whose steps of
    #: padding alone are skipped and not masked: its windows are a
    #: step's tokens and its layers are sized to fill a chip, and the
    #: routed layers' grouped products (``lax.ragged_dot``) have no
    #: batched form on the TPU
    member_axis = False

    def __post_init__(self):
        if len(self.layer_ops) != len(self.layer_ffns) or not self.layer_ops:
            raise ValueError("layer_ops and layer_ffns need one entry a layer")
        unknown = (set(self.layer_ops) - set(OPERATORS)) | (set(self.layer_ffns) - set(FFNS))
        if unknown:
            raise ValueError(f"unknown layer kinds {sorted(unknown)}")
        if not self.attention_head_dim and self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide into num_attention_heads")
        if self.router not in ROUTERS:
            raise ValueError(f"unknown router {self.router!r}; known: {ROUTERS}")
        if self.router_input not in ROUTER_INPUTS:
            raise ValueError(f"unknown router_input {self.router_input!r}; known: {ROUTER_INPUTS}")
        if self.expert_activation not in EXPERT_ACTIVATIONS:
            raise ValueError(
                f"unknown expert_activation {self.expert_activation!r}; known: {EXPERT_ACTIVATIONS}"
            )
        if not self.kv_lora_rank and (self.head_dim % 2 or self.head_dim <= 0):
            raise ValueError("the rotary embedding needs an even head width")
        if self.kv_lora_rank:
            if min(self.kv_lora_rank, self.qk_nope_head_dim, self.v_head_dim) < 1 or (
                self.qk_rope_head_dim < 2 or self.qk_rope_head_dim % 2
            ):
                raise ValueError(
                    "latent attention needs a latent, a key and a value width of a head "
                    "and an even width of the shared rotary key"
                )
            if set(self.layer_ops) - {"full_attention"} or self.qk_norm or self.attention_gate:
                raise ValueError(
                    "latent attention is full_attention in every layer, without a norm of "
                    "q and k and without a gate on its heads"
                )
            if self.layer_heads or self.num_key_value_heads != self.num_attention_heads:
                raise ValueError("latent attention expands a key and a value for every query head")
        if "sparse_attention" in self.layer_ops and (
            min(self.index_n_heads, self.index_topk, self.index_chunk) < 1
            or self.index_head_dim < 2
            or self.index_head_dim % 2
        ):
            raise ValueError("the indexer needs heads, an even head width, a top-k and blocks")
        if self.layer_heads and len(self.layer_heads) != len(self.layer_ops):
            raise ValueError("layer_heads needs one entry a layer")
        if any(heads % self.num_key_value_heads for heads in self.heads_by_layer):
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
        if "sliding_attention" in self.layer_ops and self.sliding_window < 1:
            raise ValueError("sliding_attention needs a sliding_window of at least one row")
        if self.attention_gate and "sparse_attention" in self.layer_ops:
            raise ValueError("sparse_attention has no gate on its heads")
        for op, _ in self.rope_parameters:
            rope = self.rope_of(op)
            rotated = int(self.head_dim * rope["partial_rotary_factor"])
            if op not in ATTENTIONS or rope["rope_type"] not in ROPE_TYPES:
                raise ValueError(f"rope_parameters of {op!r}: unknown operator or rope_type")
            if rotated < 2 or rotated % 2:
                raise ValueError("the rotary embedding needs an even number of rotated dimensions")
        if not (
            0 < self.experts_held
            and 0 <= self.expert_offset
            and self.expert_offset + self.experts_held <= self.num_experts
        ):
            raise ValueError(
                f"experts held {self.expert_offset}..+{self.experts_held} "
                f"are not among the {self.num_experts} published"
            )
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("num_experts_per_tok exceeds num_experts")
        if self.norm not in NORMS:
            raise ValueError(f"unknown norm {self.norm!r}; known: {NORMS}")
        for i, (op, source) in enumerate(zip(self.layer_ops, self.layer_sources)):
            if op in READS and source is None:
                raise ValueError(
                    f"layer {i} is a {op} and no {READS[op]} layer comes before it: "
                    f"it reads that layer's {'scan output' if op == 'gmu' else 'keys and values'}"
                )
        if "mamba" in self.layer_ops and min(
            self.ssm_inner, self.ssm_state, self.ssm_conv, self.ssm_dt_rank
        ) < 1:
            raise ValueError("a mamba layer needs an inner width, a state, taps and a step rank")
        if self.differential and (
            self.num_key_value_heads % 2
            or any(heads % 2 for heads in self.heads_by_layer)
            or self.kv_lora_rank or self.attention_gate or "sparse_attention" in self.layer_ops
        ):
            raise ValueError(
                "differential attention pairs neighbouring heads: an even number of query and of "
                "key/value heads, no latent, no gate and no indexer"
            )
        if "cross_attention" in self.layer_ops and not self.differential:
            raise ValueError("cross_attention is built for differential heads alone")
        if self.differential and "conv" in self.layer_ops:
            raise ValueError("a backbone of differential heads has no gated short convolution")
        if any(
            ffn != "dense" and (self.differential or op in ("mamba", "gmu"))
            for op, ffn in zip(self.layer_ops, self.layer_ffns)
        ):
            raise ValueError("mamba, gmu and differential attention layers carry the dense feed-forward")

    @property
    def layer_sources(self) -> Tuple[Optional[int], ...]:
        """For each layer the index of the earlier layer whose tensors
        it reads beside the residual (``READS``: the last layer of that
        operator before it), None for a layer that reads none."""
        sources, last = [], {}
        for i, op in enumerate(self.layer_ops):
            sources.append(last.get(READS.get(op)))
            last[op] = i
        return tuple(sources)

    @property
    def head_dim(self) -> int:
        """The width a head's scores are taken over."""
        if self.kv_lora_rank:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.attention_head_dim or self.hidden_size // self.num_attention_heads

    @property
    def kv_expanded_dim(self) -> int:
        """What a latent expands to: every head's key part and value."""
        return self.num_attention_heads * (self.qk_nope_head_dim + self.v_head_dim)

    @property
    def latent_param_count(self) -> int:
        """One latent attention: ``wq``, the projection to the latent
        and the shared rotary key, the latent's norm, the expansion to
        every head's key part and value, ``wo``."""
        h, heads, rank = self.hidden_size, self.num_attention_heads, self.kv_lora_rank
        return (
            h * heads * self.head_dim + h * (rank + self.qk_rope_head_dim) + rank
            + rank * self.kv_expanded_dim + heads * self.v_head_dim * h
        )

    @property
    def heads_by_layer(self) -> Tuple[int, ...]:
        """Query heads of each layer (what a ``conv`` layer's says is unused)."""
        return self.layer_heads or (self.num_attention_heads,) * len(self.layer_ops)

    def rope_of(self, op: str) -> Dict[str, Any]:
        """The rotary embedding of operator ``op``, HF's keys:
        ``rope_theta``, ``partial_rotary_factor`` (the leading share of a
        head that is rotated), ``rope_type`` (``default``, ``yarn`` or
        ``none``: not rotated) and, for YaRN, ``factor``, ``original_max_position_embeddings``,
        ``beta_fast``, ``beta_slow``, ``attention_factor``."""
        stated = dict(dict(self.rope_parameters).get(op, ()))
        return {
            "rope_theta": self.rope_theta, "partial_rotary_factor": 1.0, "rope_type": "default",
            **stated,
        }

    @property
    def indexer_param_count(self) -> int:
        """The indexer of one ``sparse_attention`` layer: its query and
        key projections, the key's LayerNorm, the head weights."""
        h, width = self.hidden_size, self.index_head_dim
        return h * self.index_n_heads * width + h * width + 2 * width + h * self.index_n_heads

    def init_fn(self):
        from .backbone import init_backbone

        return init_backbone

    def forward_fn(self):
        from .backbone import forward_backbone

        return forward_backbone

    def forward_aux_fn(self):
        """The forward with its counters where a layer has any: a
        routed layer, an indexer, a scan, or an attention in tiles (a
        ``sliding_attention``, or a window longer than a tile)."""
        from .backbone import attends_in_tiles, forward_backbone_aux

        counted = any(op == "mamba" or attends_in_tiles(op, self.lookback_window) for op in self.layer_ops)
        return forward_backbone_aux if "moe" in self.layer_ffns or counted else None

    def fit_counter_attrs(self, counters: Dict[str, Any]) -> Dict[str, Any]:
        """The counters, and what of the spec a reader needs to read
        them: beside the router counts which of the published experts
        are held here (who reads ``router_tokens`` needs the three);
        beside the selection's counts, how many keys a query may keep;
        beside ``scan_steps`` the scan's sizes and which layers read an
        earlier layer's tensors; how many layers' tile outputs the
        backward pass is handed by name (``backbone.tile_outputs_kept``:
        0 where the program rematerialises nothing or runs no tile
        loop); and what every program of the spec says
        (:meth:`program_attrs`)."""
        from .backbone import scan_chunk_rows, tile_outputs_kept

        attrs = {**super().fit_counter_attrs(counters), **self.program_attrs()}
        attrs["tile_outputs_kept"] = tile_outputs_kept(self)
        if "moe" in self.layer_ffns:
            attrs.update(
                num_experts=self.num_experts,
                experts_held=self.experts_held,
                expert_offset=self.expert_offset,
            )
        if "mamba" in self.layer_ops:
            reads = lambda op: [  # noqa: E731
                source for o, source in zip(self.layer_ops, self.layer_sources) if o == op
            ]
            attrs.update(
                ssm_inner=self.ssm_inner,
                ssm_state=self.ssm_state,
                scan_chunk=scan_chunk_rows(self.lookback_window),
                memory_width=self.ssm_inner if "gmu" in self.layer_ops else 0,
                memory_reads=reads("gmu"),
                kv_reads=reads("cross_attention"),
            )
        if "sparse_attention" in self.layer_ops:
            attrs["index_topk"] = self.index_topk
        if self.kv_lora_rank:  # what a row keeps of itself, and what that expands to
            attrs.update(
                kv_lora_rank=self.kv_lora_rank,
                qk_rope_head_dim=self.qk_rope_head_dim,
                v_head_dim=self.v_head_dim,
                kv_expanded_dim=self.kv_expanded_dim,
            )
        return attrs

    def program_attrs(self) -> Dict[str, Any]:
        """Of a spec with ``sparse_attention`` layers, in its fit and its
        predict programs alike: ``selection_blocks_searched``, the blocks
        of queries a window a layer whose selection searches for a k-th
        largest score (``backbone.selection_blocks_searched``: static,
        by the function ``select_keys`` decides with; 0 where
        ``index_topk`` is at least the window)."""
        if "sparse_attention" not in self.layer_ops:
            return {}
        from .backbone import selection_blocks_searched

        return {"selection_blocks_searched": selection_blocks_searched(self)}

    def layer_param_count(self, op: str, ffn: str, heads: Optional[int] = None) -> int:
        """One block of ``heads`` query heads (None: ``num_attention_heads``):
        two norms, its operator, its feed-forward (the expert bias is a
        buffer, not a parameter)."""
        h = self.hidden_size
        heads = self.num_attention_heads if heads is None else heads
        kv = self.num_key_value_heads * self.head_dim
        total = 2 * h * (2 if self.norm == "layer" else 1)
        if op == "conv":
            total += h * 3 * h + h * self.conv_L_cache + h * h
        elif op == "mamba":
            total += self.mamba_param_count
        elif op == "gmu":  # the gate's projection and the output's
            total += 2 * h * self.ssm_inner
        elif self.kv_lora_rank:
            total += self.latent_param_count
        else:
            qo = heads * self.head_dim
            keys = 0 if op == "cross_attention" else 2  # a cross layer projects no key and no value
            total += 2 * h * qo + keys * h * kv
            total += 2 * self.head_dim * self.qk_norm + h * heads * self.attention_gate
            total += (qo + keys * kv + h) * self.attention_bias
            # the four vectors of the pair's weight and the norm over the difference
            total += 6 * self.head_dim * self.differential
        if op == "sparse_attention":
            total += self.indexer_param_count
        if ffn == "dense":
            return total + 3 * h * self.intermediate_size
        shared = 3 * h * self.shared_expert_intermediate_size
        return total + h * self.num_experts + self.experts_held * 3 * h * self.moe_intermediate_size + shared

    @property
    def mamba_param_count(self) -> int:
        """One ``mamba`` operator: the input projection to the stream
        and its gate, the taps and their bias, the projection to the
        step's rank and the row's two matrices, the step's projection
        and bias, ``A_log``, ``D``, the output projection."""
        h, d, n, rank = self.hidden_size, self.ssm_inner, self.ssm_state, self.ssm_dt_rank
        return (
            h * 2 * d + d * self.ssm_conv + d + d * (rank + 2 * n) + rank * d + d + d * n + d + d * h
        )

    def param_count(self) -> int:
        h = self.hidden_size
        layers = sum(
            self.layer_param_count(op, ffn, heads)
            for op, ffn, heads in zip(self.layer_ops, self.layer_ffns, self.heads_by_layer)
        )
        embed = self.n_features * h + h
        head = h * (2 if self.norm == "layer" else 1) + h * self.n_features_out + self.n_features_out
        return embed + layers + head

    def flops_per_sample(self) -> float:
        """One window of ``lookback_window`` tokens: products only,
        causal attention at its useful half (sliding attention at the
        keys inside its window, sparse attention at the keys a query
        keeps, its indexer over every causal key), the expert layer at
        the pairs this holder expects under even routing; a scan at the
        elementwise operations of a row's state update (no product, but
        the planner's cost of a step has to hold them)."""
        h, t = self.hidden_size, self.lookback_window
        kv = self.num_key_value_heads * self.head_dim
        index = self.index_n_heads * self.index_head_dim
        # keys a query keeps, on average over a window: min(t + 1, top-k)
        kept = min(t, self.index_topk)
        kept_mean = (kept * (kept + 1) / 2.0 + (t - kept) * self.index_topk) / t
        per_token = 2.0 * self.n_features * h
        local_pairs = self.num_experts_per_tok * self.experts_held / self.num_experts
        for op, ffn, heads in zip(self.layer_ops, self.layer_ffns, self.heads_by_layer):
            qo = heads * self.head_dim
            if op == "conv":
                per_token += 2.0 * h * 3 * h + 2.0 * h * h + 2.0 * self.conv_L_cache * h
                attended = 0.0
            elif op == "mamba":  # every matrix and the taps; 7 operations a state entry a row, 3 a channel
                d, n = self.ssm_inner, self.ssm_state
                per_token += 2.0 * (self.mamba_param_count - 3 * d - d * n) + 7.0 * d * n + 3.0 * d
                attended = 0.0
            elif op == "gmu":
                per_token += 4.0 * h * self.ssm_inner
                attended = 0.0
            elif op in ("full_attention", "cross_attention"):
                attended = t / 2.0
            elif op == "sliding_attention":
                reach = min(t, self.sliding_window)  # keys of a query: min(t + 1, window)
                attended = (reach * (reach + 1) / 2.0 + (t - reach) * reach) / t
            else:
                attended = kept_mean
                per_token += 2.0 * h * (index + self.index_head_dim + self.index_n_heads)
                per_token += (t + 1.0) * index  # 2 x index a causal pair, (t + 1) / 2 pairs
            if self.kv_lora_rank:  # every matrix of the latent attention; a score and a value a pair
                per_token += 2.0 * (self.latent_param_count - self.kv_lora_rank)
                per_token += 2.0 * attended * heads * (self.head_dim + self.v_head_dim)
            elif op not in ("conv", "mamba", "gmu"):
                # q, o, the gate, and k, v but in a cross layer; a score and
                # a value a pair (a differential pair: two maps over a
                # value twice as wide)
                keys = 0 if op == "cross_attention" else 2
                per_token += 2.0 * h * (2 * qo + keys * kv + heads * self.attention_gate)
                per_token += (6.0 if self.differential else 4.0) * attended * qo
            if ffn == "dense":
                per_token += 6.0 * h * self.intermediate_size
            else:
                per_token += 2.0 * h * self.num_experts
                per_token += local_pairs * 6.0 * h * self.moe_intermediate_size
                per_token += 6.0 * h * self.shared_expert_intermediate_size
        return per_token * t + 2.0 * h * self.n_features_out


# ---------------------------------------------------------------------------
# Raw layer-list definitions (the KerasRawModelRegressor analog): config
# files can describe a Sequential stack of Dense layers which compiles down
# to a FeedForwardSpec.
# ---------------------------------------------------------------------------


@dataclass
class Dense:
    units: int
    activation: str = "linear"
    l1_activity: float = 0.0
    # Accepted for Keras-config compatibility; the input dim is inferred at
    # fit time from the data.
    input_shape: Optional[Tuple[int, ...]] = None
    input_dim: Optional[int] = None

    def get_params(self, deep: bool = False) -> dict:
        return {
            "units": self.units,
            "activation": self.activation,
            "l1_activity": self.l1_activity,
        }


class Sequential:
    """
    Layer-list container recognized by the serializer (the analog of
    ``tensorflow.keras.Sequential`` in raw-spec configs —
    serializer/from_definition.py special-cases it via
    ``_serializer_layers_container``).
    """

    _serializer_layers_container = True

    def __init__(self, layers, optimizer="Adam", optimizer_kwargs=None, loss="mse"):
        self.layers = list(layers)
        self.optimizer = optimizer
        self.optimizer_kwargs = optimizer_kwargs or {}
        self.loss = loss

    def get_params(self, deep: bool = False) -> dict:
        return {
            "layers": self.layers,
            "optimizer": self.optimizer,
            "optimizer_kwargs": self.optimizer_kwargs,
            "loss": self.loss,
        }

    def compile_spec(self, n_features: int) -> FeedForwardSpec:
        """Compile the layer list into a FeedForwardSpec for ``n_features``
        inputs; the final Dense layer becomes the output head."""
        dense_layers = [layer for layer in self.layers if isinstance(layer, Dense)]
        if len(dense_layers) != len(self.layers):
            raise ValueError(
                "Only Dense layers are supported in raw Sequential specs; got "
                f"{[type(l).__name__ for l in self.layers]}"
            )
        if not dense_layers:
            raise ValueError("Sequential spec needs at least one Dense layer")
        hidden, head = dense_layers[:-1], dense_layers[-1]
        return FeedForwardSpec(
            n_features=n_features,
            n_features_out=head.units,
            dims=tuple(layer.units for layer in hidden),
            activations=tuple(layer.activation for layer in hidden),
            out_activation=head.activation,
            l1_activity=tuple(layer.l1_activity for layer in hidden)
            if any(layer.l1_activity for layer in hidden)
            else (),
            optimizer=OptimizerSpec.from_config(self.optimizer, self.optimizer_kwargs),
            loss=self.loss,
        )
