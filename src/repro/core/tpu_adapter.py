"""TPU adaptation of the paper's technique: predict multi-pod step time by
replaying a fine-grained op DAG under a link-sharing model.

The paper's insight — *multi-node step time is predictable from single-node
fine-grained traces replayed under a bandwidth-sharing DES* — has no literal
gRPC/PS analogue on TPU pods, so the mapping is (DESIGN.md §3):

  PS downlink/uplink   ->  per-axis ICI lanes (all-gather / reduce-scatter)
  PS update phase      ->  optimizer fusion segment (on-device)
  HTTP/2 WIN chunking  ->  chunked collectives interleaving with compute
  worker compute       ->  per-layer MXU segments
  cross-pod            ->  DCN all-reduce of (possibly compressed) grads

``build_step_dag`` constructs the per-layer op DAG of one training step from
a :class:`ModelConfig` + mesh factors (the TPU analogue of the paper's
per-layer TensorFlow trace: layer-granular compute, per-layer gradient
reduce-scatter eligible as soon as that layer's backward completes).  The
paper's Algorithm 3.1 simulator then predicts the step time, including
compute/collective overlap — this drives ``launch/whatif.py`` (straggler,
scale-out and compression what-ifs, the paper's §4 scheduler use-case).

Calibration hook: ``calibrate`` rescales the DAG's compute segments so the
summed compute matches ``cost_analysis()`` FLOPs of the real compiled step
(profile-once, predict-many — same as the paper's 1-worker profiling).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

from repro.core.events import Op, ResourceSpec, StepTemplate, LINK, COMPUTE
from repro.core.simulator import SimConfig, Simulation
from repro.core.hlo_analysis import (DCN_BW, HBM_BW, ICI_BW, ICI_LINKS,
                                     PEAK_FLOPS)
from repro.models.config import MOE_KINDS, ModelConfig


@dataclass(frozen=True)
class MeshFactors:
    data: int = 16
    model: int = 16
    pods: int = 1
    mfu: float = 0.5           # sustained fraction of peak on MXU segments

    @property
    def chips(self) -> int:
        return self.data * self.model * self.pods


def tpu_resources(num_pods: int = 1) -> Dict[str, ResourceSpec]:
    res = {
        "mxu": ResourceSpec("mxu", COMPUTE),
        "vpu": ResourceSpec("vpu", COMPUTE),
        # ICI lanes modelled per direction like the paper's downlink/uplink
        "ici_ag": ResourceSpec("ici_ag", LINK, ICI_LINKS * ICI_BW),
        "ici_rs": ResourceSpec("ici_rs", LINK, ICI_LINKS * ICI_BW),
    }
    if num_pods > 1:
        res["dcn"] = ResourceSpec("dcn", LINK, DCN_BW)
    return res


def _layer_param_bytes(cfg: ModelConfig) -> List[Tuple[str, float, float]]:
    """Per layer: (kind, param bytes, active fraction)."""
    out = []
    d, f = cfg.d_model, cfg.d_ff
    bytes_per = 2.0  # bf16
    for li in range(cfg.n_layers):
        kind = cfg.pattern[li % len(cfg.pattern)]
        attn = (d * cfg.n_heads * cfg.head_dim * 2
                + d * cfg.n_kv * cfg.head_dim * 2)
        if kind in MOE_KINDS:
            m = cfg.moe
            fe = cfg.d_expert_eff
            routed = m.num_experts * 3 * d * fe
            shared = m.num_shared * 3 * d * fe + (
                3 * d * cfg.dense_residual_ff if cfg.dense_residual_ff else 0)
            params = attn + routed + shared
            active = (attn + m.top_k * 3 * d * fe + shared) / params
        elif kind in ("slstm", "mlstm"):
            params = d * d * 6  # projections + gates (approx)
            active = 1.0
        elif kind == "rglru":
            r = cfg.rnn_width
            params = d * r * 2 + r * r * 2 + r * d + 3 * d * f
            active = 1.0
        else:
            glu = 3 if cfg.mlp in ("swiglu", "geglu") else 2
            params = attn + glu * d * f
            if kind in ("xattn", "encdec"):
                params += attn
            active = 1.0
        out.append((kind, params * bytes_per, active))
    return out


def build_step_dag(cfg: ModelConfig, mesh: MeshFactors, tokens_global: int,
                   chunk_layers: int = 1,
                   compressed_dcn: float = 1.0) -> StepTemplate:
    """One training step as an op DAG (per-device quantities).

    fwd_i needs param all-gather_i (FSDP); bwd_i (reverse order) needs the
    same gather; grad reduce-scatter_i is eligible right after bwd_i — the
    exact structure of the paper's Fig. 6, with {downlink, uplink} replaced
    by {ici_ag, ici_rs}.  With ``pods > 1`` a DCN all-reduce per layer
    follows the reduce-scatter (optionally compressed).
    """
    layers = _layer_param_bytes(cfg)
    tokens_dev = tokens_global / (mesh.data * mesh.pods)
    flops_rate = PEAK_FLOPS * mesh.mfu
    ops: List[Op] = []
    idx: Dict[Tuple[str, int], int] = {}

    def add(op: Op, key) -> int:
        ops.append(op)
        idx[key] = len(ops) - 1
        return len(ops) - 1

    L = len(layers)
    for i, (kind, pbytes, active) in enumerate(layers):
        shard_bytes = pbytes / mesh.chips            # FSDP-resident shard
        # all-gather of the layer's params over the fsdp axis (per device
        # wire bytes: (n-1)/n of the tp-sharded full layer)
        n = mesh.data
        ag_bytes = (pbytes / mesh.model) * (n - 1) / n
        add(Op(name=f"ag/{i}", res="ici_ag", size=ag_bytes,
               tags={"layer": i}), ("ag", i))
        # forward compute: 2 * active_params * tokens FLOPs on this device
        fwd_flops = 2.0 * (pbytes / 2.0) * active * tokens_dev / mesh.model
        deps = [idx[("ag", i)]]
        if i > 0:
            deps.append(idx[("fwd", i - 1)])
        add(Op(name=f"fwd/{i}", res="mxu", duration=fwd_flops / flops_rate,
               deps=tuple(deps), tags={"layer": i}), ("fwd", i))
    for i in range(L - 1, -1, -1):
        kind, pbytes, active = layers[i]
        bwd_flops = 4.0 * (pbytes / 2.0) * active * \
            (tokens_global / (mesh.data * mesh.pods)) / mesh.model
        deps = [idx[("fwd", L - 1)]] if i == L - 1 else [idx[("bwd", i + 1)]]
        # re-gather for bwd (remat path) — eligible in parallel with bwd i+1
        ag2 = add(Op(name=f"ag2/{i}", res="ici_ag",
                     size=(pbytes / mesh.model) * (mesh.data - 1) / mesh.data,
                     deps=(idx[("fwd", L - 1)],) if i == L - 1 else
                     (idx[("bwd", i + 1)],),
                     tags={"layer": i}), ("ag2", i))
        add(Op(name=f"bwd/{i}", res="mxu",
               duration=bwd_flops / (PEAK_FLOPS * mesh.mfu),
               deps=tuple(deps) + (ag2,), tags={"layer": i}), ("bwd", i))
        n = mesh.data
        rs_bytes = (pbytes / mesh.model) * (n - 1)  # unscattered input
        add(Op(name=f"rs/{i}", res="ici_rs", size=rs_bytes / n * n,
               deps=(idx[("bwd", i)],), tags={"layer": i}), ("rs", i))
        if mesh.pods > 1:
            dcn_bytes = (pbytes / mesh.chips) * 2 * compressed_dcn
            add(Op(name=f"dcn/{i}", res="dcn", size=dcn_bytes,
                   deps=(idx[("rs", i)],), tags={"layer": i}), ("dcn", i))
        # optimizer segment (the paper's "update phase", now on-device VPU)
        upd_dep = ("dcn", i) if mesh.pods > 1 else ("rs", i)
        add(Op(name=f"opt/{i}", res="vpu",
               duration=3.0 * (pbytes / mesh.chips) / HBM_BW,
               deps=(idx[upd_dep],), tags={"layer": i}), ("opt", i))
    return StepTemplate(ops=ops, meta={"arch": cfg.name,
                                       "tokens": tokens_global,
                                       "chips": mesh.chips})


def calibrate(dag: StepTemplate, hlo_flops_per_device: float,
              mfu: float = 0.5) -> StepTemplate:
    """Rescale MXU segments so total compute matches the compiled step."""
    total = sum(op.duration for op in dag.ops if op.res == "mxu")
    target = hlo_flops_per_device / (PEAK_FLOPS * mfu)
    if total <= 0:
        return dag
    scale = target / total
    ops = [Op(name=o.name, res=o.res, size=o.size,
              duration=o.duration * (scale if o.res == "mxu" else 1.0),
              deps=o.deps, priority=o.priority, tags=dict(o.tags))
           for o in dag.ops]
    return StepTemplate(ops=ops, meta=dict(dag.meta))


def predict_step_time(dag: StepTemplate, num_pods: int = 1,
                      straggler_factor: float = 1.0,
                      link_policy: str = "fifo",
                      win_bytes: float = 0.0,
                      seed: int = 0) -> float:
    """DES-predicted step time (seconds).

    ``straggler_factor > 1`` slows one simulated worker's compute (the
    paper's heterogeneity what-if); ``win_bytes > 0`` switches the link
    scheduler to the paper's WIN-chunked multiplexing model (chunked
    collectives interleaving with compute).
    """
    steps = [dag]
    if straggler_factor != 1.0:
        slow_ops = [Op(name=o.name, res=o.res, size=o.size,
                       duration=o.duration * straggler_factor, deps=o.deps,
                       priority=o.priority, tags=dict(o.tags))
                    for o in dag.ops]
        steps = [StepTemplate(ops=slow_ops, meta=dict(dag.meta))]
    cfg = SimConfig(
        resources=tpu_resources(num_pods),
        link_policy=("http2" if win_bytes > 0 else link_policy),
        win=win_bytes or 28e6,
        steps_per_worker=6,
        warmup_steps=2,
        seed=seed,
    )
    sim = Simulation(cfg)
    trace = sim.run(steps, num_workers=1, sample=False)
    comps = sorted(t for _w, _s, t in trace.step_completions)
    if len(comps) < 3:
        return comps[-1] if comps else float("inf")
    # steady-state per-step time after the first step
    return (comps[-1] - comps[1]) / (len(comps) - 2)
