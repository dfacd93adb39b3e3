"""Analytic 8->64-chip scaling model driven by the collective audit.

A single-chip environment cannot measure multi-chip scaling (BASELINE
north star 3: 8->64-chip scaling efficiency), so this module provides
the best evidence that environment permits: each benchmark config is
compiled — NOT executed — for real 8/16/64-device meshes at its real
benchmark shapes, the compiled HLO's collectives are inventoried per
mesh axis by `collective_audit` (bytes x counts), and a stated
interconnect model converts those bytes into per-step communication
time, which combines with the measured single-chip step time into a
predicted scaling efficiency. Every term is inspectable: the bytes
come from the actual compiled programs, the constants are published
v5e figures, and the combination rule is ~15 lines below.

Reference anchor: the measured VGG-16 cluster scaling tables the
reference publishes (benchmark/cluster/vgg16/README.md:96-130 — 78.6%
at 20 trainers degrading to 60.9% at 100); this model is the
TPU-native analog of that table for the same "how far from linear is
the layout" question.

The MODEL, stated:
- Each mesh axis rides ICI (v5e: a 2D torus; a <=256-chip slice needs
  no DCN hop, so all 8/64-chip layouts here are ICI-only). Per-chip,
  per-axis, one-way ICI bandwidth `ICI_BW`; per-hop latency `ICI_LAT`.
  DCN constants are carried for completeness (multi-slice layouts
  would map their outermost axis onto DCN).
- Ring algorithms over an axis of size N move, per chip:
    all-reduce          2*B*(N-1)/N        (B = full result bytes)
    all-gather            B*(N-1)/N        (B = gathered result bytes)
    reduce-scatter        B*(N-1)          (B = shard result bytes)
    all-to-all            B*(N-1)/N        (B = result bytes)
    collective-permute    B                (one hop)
  plus per-occurrence hop latency ((N-1) hops; 2(N-1) for all-reduce).
  A collective attributed to a composite axis set uses the product of
  those axis sizes as its N (it spans that subgrid).
- Collectives are assumed serialized with each other, and two bounds
  are reported against the measured single-chip compute time T_c:
    eff_serial  = T_c / (T_c + T_comm)   (no compute/comm overlap)
    eff_overlap = T_c / max(T_c, T_comm) (perfect overlap)
  Real XLA schedules land between the two.
- T_c comes from the MEASURED single-chip benchmark throughput
  (round-4 chip runs, this repo — see ANCHORS) scaled to the per-chip workload of the
  layout: compute partitioning is taken as ideal, so ALL predicted
  loss comes from communication — which is exactly what the audit can
  see. FLOP-imbalance/recompute effects are out of scope and stated.
"""
from __future__ import annotations

import json
from typing import Dict, List, Optional, Tuple

import numpy as np

# ---- v5e interconnect + chip constants (per chip) --------------------
ICI_BW = 4.5e10      # bytes/s one-way per torus axis (45 GB/s)
ICI_LAT = 1e-6       # s per ICI hop
DCN_BW = 3.125e9     # bytes/s per chip (25 Gbit/s/chip host NIC share)
DCN_LAT = 10e-6      # s per DCN hop
# FLOP/s — this model is OF a v5e pod whatever device runs it, so it
# reads the v5e row of the one peak table (shared with the live-MFU
# gauge so the two can't drift)
from ..observability.attribution import PEAK_FLOPS_BY_DEVICE_KIND
PEAK_BF16 = PEAK_FLOPS_BY_DEVICE_KIND["TPU v5 lite"]

# Measured single-chip anchors (round-4 chip runs, real v5e):
# (unit, per-replica batch in that unit, measured units/sec/chip).
# deepfm uses the round-4 in-graph-scan measurement (590937, 0.9%
# spread) — the round-3 888k carried a 32.6% spread and a re-run of
# that noisy protocol on identical code swung to 428k (57.6%), i.e.
# both bracket the trustworthy number rather than contradicting it.
ANCHORS = {
    "resnet50": ("images", 128, 2576.86),
    "transformer": ("tokens", 32 * 256, 206540.0),
    "transformer_dp": ("tokens", 32 * 256, 206540.0),
    "deepfm": ("examples", 2048, 590937.0),
}


def _collective_time(kind: str, total_bytes: int, count: int, n: int,
                     bw: float = ICI_BW, lat: float = ICI_LAT) -> float:
    """Per-step seconds for `count` occurrences of `kind` moving
    `total_bytes` (sum of audited result-shape bytes) over an axis
    group of size n, per the ring model in the module docstring."""
    if n <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2 * total_bytes * (n - 1) / n / bw + count * 2 * (n - 1) * lat
    if kind == "all-gather":
        return total_bytes * (n - 1) / n / bw + count * (n - 1) * lat
    if kind == "reduce-scatter":
        return total_bytes * (n - 1) / bw + count * (n - 1) * lat
    if kind == "all-to-all":
        return total_bytes * (n - 1) / n / bw + count * (n - 1) * lat
    if kind == "collective-permute":
        return total_bytes / bw + count * lat
    return total_bytes / bw


def predict(inv, mesh_axis_sizes: Dict[str, int], t_comp: float,
            bw: float = ICI_BW, lat: float = ICI_LAT) -> Dict:
    """Combine an audit inventory with the interconnect model.

    inv: {(kind, axes): (count, bytes)} from collective_audit.inventory
    mesh_axis_sizes: {axis_name: size}
    t_comp: measured-anchor single-chip compute seconds per step
    bw/lat: ICI constants — overridable for sensitivity sweeps
    """
    out = predict_multihost(inv, mesh_axis_sizes, t_comp, hosts=1,
                            bw=bw, lat=lat)
    for k in ("hosts", "chips_per_host", "t_dcn_ms"):
        out.pop(k)
    return out


# ---------------------------------------------------------------------
# Compile-only HLO extraction: build the program, run ONLY the startup
# (host-side init), compile the train step AOT at the benchmark shapes
# for the target mesh, and audit it. No multi-device execution happens,
# which is what makes 64-device bench-shape audits affordable on the
# CPU backend (a 64-virtual-device tiny RUN of ResNet-50 costs ~450s;
# the AOT compile alone costs ~40s).
# ---------------------------------------------------------------------

def predict_multihost(inv, mesh_axis_sizes: Dict[str, int],
                      t_comp: float, hosts: int,
                      dcn_axis: str = "data",
                      bw: float = ICI_BW, lat: float = ICI_LAT) -> Dict:
    """Two-tier (ICI intra-host + DCN inter-host) prediction — the
    multi-host continuation of `predict`, answering the question the
    reference answered with its multi-host pserver tables
    (benchmark/cluster/vgg16/README.md:96-130).

    Layout convention (the standard one): model/seq axes live INSIDE a
    host; only the `dcn_axis` (data parallelism) spans hosts. A
    collective whose axis set includes `dcn_axis` decomposes
    hierarchically — for all-reduce, the canonical 3 phases:
    reduce-scatter over the intra-host group g (ICI), all-reduce of
    each 1/g shard across H hosts (each chip's shard rides its own
    host-NIC share, DCN), all-gather over g (ICI) — ICI bytes equal
    the flat ring's, DCN moves 2*(B/g)*(H-1)/H per chip. Other kinds
    are charged their full ring cost at BOTH tiers (shard bytes across
    DCN) — conservative. Axes without `dcn_axis` stay pure ICI."""
    per_axis: Dict[str, float] = {}
    t_comm = t_dcn_total = 0.0
    for (kind, axes), (count, b) in inv.items():
        if axes in (("?",), ("local",)):
            continue
        n = int(np.prod([mesh_axis_sizes[a] for a in axes]))
        if dcn_axis in axes and hosts > 1:
            # the DATA axis is what spans hosts (layout convention
            # above) — its size must divide into them, or the layout
            # cannot exist and mis-pricing it would be silent
            assert mesh_axis_sizes[dcn_axis] % hosts == 0, (
                dcn_axis, mesh_axis_sizes[dcn_axis], hosts)
            g = n // hosts
            t_ici = _collective_time(kind, b, count, g, bw=bw, lat=lat)
            t_dcn = _collective_time(kind, b // g, count, hosts,
                                     bw=DCN_BW, lat=DCN_LAT)
            t = t_ici + t_dcn
            t_dcn_total += t_dcn
        else:
            t = _collective_time(kind, b, count, n, bw=bw, lat=lat)
        t_comm += t
        for a in axes:
            per_axis[a] = per_axis.get(a, 0.0) + t
    return {
        "hosts": hosts,
        "chips_per_host": int(np.prod(
            list(mesh_axis_sizes.values()))) // hosts,
        "t_comp_ms": round(t_comp * 1e3, 3),
        "t_comm_ms": round(t_comm * 1e3, 3),
        "t_dcn_ms": round(t_dcn_total * 1e3, 3),
        "per_axis_ms": {a: round(t * 1e3, 3)
                        for a, t in sorted(per_axis.items())},
        "eff_serial": round(t_comp / (t_comp + t_comm), 4),
        "eff_overlap": round(t_comp / max(t_comp, t_comm), 4),
    }


def aot_compiled_hlo(pexe, program, feed_structs: Dict, fetch_list,
                     scope=None) -> str:
    """Compiled HLO of `program` on pexe's mesh at the shapes/dtypes in
    `feed_structs` (name -> jax.ShapeDtypeStruct), without executing a
    step. State shapes come from the scope (startup must have run)."""
    import jax
    import jax.numpy as jnp
    from ..core.scope import global_scope

    desc = program.desc if hasattr(program, "desc") else program
    scope = global_scope() if scope is None else scope
    block = desc.block(0)
    fetch_names = [f if isinstance(f, str) else f.name
                   for f in fetch_list]
    sig = tuple(sorted((k, (tuple(v.shape), str(v.dtype)))
                       for k, v in feed_structs.items()))
    cp = pexe._compile(desc, block, sig, fetch_names, scope)

    def struct(x):
        a = np.asarray(x) if not hasattr(x, "shape") else x
        return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)

    # the mesh step takes sequences, in its names' order
    ro = [struct(scope.get(n)) for n in cp.ro_names]
    rw = [struct(scope.get(n)) for n in cp.rw_names]
    lowered = cp.jitted.lower([feed_structs[k] for k in cp.feed_names],
                              ro, rw, jax.ShapeDtypeStruct((), jnp.int32))
    return lowered.compile().as_text()


def _mesh_rule_transformer(n: int) -> Tuple[int, int, int]:
    """(data, seq, model) — same widening rule as dryrun_multichip."""
    if n % 64 == 0:
        sp, tp = 4, 4
    elif n % 8 == 0:
        sp, tp = 2, 2
    else:
        sp, tp = 1, 2
    return n // (sp * tp), sp, tp


def _config_resnet(n: int, devices):
    """ResNet-50 bs128/chip pure DP (the headline config)."""
    import jax
    import paddle_tpu as pt
    from jax.sharding import PartitionSpec as P  # noqa: F401
    from ..models import resnet
    from . import make_mesh
    from .executor import ParallelExecutor, ShardingSpec

    pt.reset_default_programs()
    pt.reset_global_scope()
    pt.amp.enable(True)
    mesh = make_mesh((n,), ("data",), devices=devices[:n])
    main, startup, f = resnet.build_train(class_dim=1000, depth=50,
                                          lr=0.1)
    pexe = ParallelExecutor(mesh=mesh,
                            sharding=ShardingSpec(feed_axis="data"))
    pt.Executor().run(startup)
    batch = 128 * n
    feeds = {
        "img": jax.ShapeDtypeStruct((batch, 3, 224, 224), np.float32),
        "label": jax.ShapeDtypeStruct((batch, 1), np.int64),
    }
    hlo = aot_compiled_hlo(pexe, main, feeds, [f["loss"]])
    return hlo, mesh, {"data": n}


def _config_transformer(n: int, devices):
    """Transformer-base NMT at bench dims (d512, 6 layers, 32k vocab,
    len 256, bs32/replica) over dp x sp(ring) x tp with row-sharded
    embeddings — the dryrun layout at benchmark scale."""
    import jax
    import paddle_tpu as pt
    from jax.sharding import PartitionSpec as P
    from ..models import transformer
    from . import make_mesh
    from .executor import ParallelExecutor, ShardingSpec

    pt.reset_default_programs()
    pt.reset_global_scope()
    pt.amp.enable(True)
    dp, sp, tp = _mesh_rule_transformer(n)
    mesh = make_mesh((dp, sp, tp), ("data", "seq", "model"),
                     devices=devices[:n])
    vocab, max_len, d_model = 32000, 256, 512
    main, startup, f = transformer.build_train(
        src_vocab=vocab, trg_vocab=vocab, max_len=max_len, n_layer=6,
        n_head=8, d_model=d_model, d_inner=2048, lr=1e-3,
        seq_axis="seq" if sp > 1 else None, seq_impl="ring",
        dist_embedding=tp > 1)
    specs = transformer.tp_param_specs(
        main, vocab_sizes=(vocab,) if tp > 1 else ())
    sharding = ShardingSpec(specs=specs, feed_axis="data")
    sharding.specs["pos_ids"] = P()
    pexe = ParallelExecutor(mesh=mesh, sharding=sharding)
    pt.Executor().run(startup)
    batch = 32 * dp
    ids = jax.ShapeDtypeStruct((batch, max_len, 1), np.int64)
    feeds = {"src_ids": ids, "trg_ids": ids, "trg_labels": ids,
             "pos_ids": jax.ShapeDtypeStruct((max_len,), np.int64)}
    hlo = aot_compiled_hlo(pexe, main, feeds, [f["loss"]])
    return hlo, mesh, {"data": dp, "seq": sp, "model": tp}


def _config_transformer_dp(n: int, devices):
    """The SAME transformer at pure DP — the layout-selection
    comparison the model exists to inform: at transformer-base scale
    (d512, bs32/replica) the Megatron TP pairs + ring attention move
    far more bytes than one gradient all-reduce, so DP dominates at
    8-64 chips (TP/SP pay off only when the model no longer fits or
    per-chip batch saturates). Keeping both layouts in the report
    makes that tradeoff a stated, numbered conclusion."""
    import jax
    import paddle_tpu as pt
    from ..models import transformer
    from . import make_mesh
    from .executor import ParallelExecutor, ShardingSpec

    pt.reset_default_programs()
    pt.reset_global_scope()
    pt.amp.enable(True)
    mesh = make_mesh((n,), ("data",), devices=devices[:n])
    vocab, max_len = 32000, 256
    main, startup, f = transformer.build_train(
        src_vocab=vocab, trg_vocab=vocab, max_len=max_len, n_layer=6,
        n_head=8, d_model=512, d_inner=2048, lr=1e-3)
    pexe = ParallelExecutor(mesh=mesh,
                            sharding=ShardingSpec(feed_axis="data"))
    pt.Executor().run(startup)
    batch = 32 * n
    ids = jax.ShapeDtypeStruct((batch, max_len, 1), np.int64)
    feeds = {"src_ids": ids, "trg_ids": ids, "trg_labels": ids,
             "pos_ids": jax.ShapeDtypeStruct((max_len,), np.int64)}
    hlo = aot_compiled_hlo(pexe, main, feeds, [f["loss"]])
    return hlo, mesh, {"data": n}


def _config_deepfm(n: int, devices, num_features=int(1e5)):
    """DeepFM CTR bs2048/replica, embedding tables row-sharded over a
    'model' (EP) axis — BASELINE config 5's pserver-replacement
    layout."""
    import jax
    import paddle_tpu as pt
    from jax.sharding import PartitionSpec as P
    from ..models import deepfm
    from . import make_mesh
    from .executor import ParallelExecutor, ShardingSpec

    pt.reset_default_programs()
    pt.reset_global_scope()
    pt.amp.enable(False)      # bench runs deepfm in f32
    ep = 4 if n % 4 == 0 and n >= 16 else 2
    dp = n // ep
    mesh = make_mesh((dp, ep), ("data", "model"), devices=devices[:n])
    main, startup, f = deepfm.build_train(num_features=num_features,
                                          num_fields=39,
                                          distributed=True)
    specs = {p.name: P("model", None) for p in main.all_parameters()
             if len(p.shape or ()) == 2 and p.shape[0] == num_features}
    pexe = ParallelExecutor(
        mesh=mesh, sharding=ShardingSpec(specs=specs, feed_axis="data"))
    pt.Executor().run(startup)
    batch = 2048 * dp
    feeds = {
        "feat_ids": jax.ShapeDtypeStruct((batch, 39, 1), np.int64),
        "feat_vals": jax.ShapeDtypeStruct((batch, 39), np.float32),
        "label": jax.ShapeDtypeStruct((batch, 1), np.float32),
    }
    hlo = aot_compiled_hlo(pexe, main, feeds, [f["loss"]])
    return hlo, mesh, {"data": dp, "model": ep}


def _t_comp(config: str, axis_sizes: Dict[str, int]) -> float:
    """Measured-anchor compute seconds/step for the layout: per-chip
    workload over the measured single-chip rate (ideal FLOP
    partitioning — all predicted degradation is communication)."""
    unit, per_replica, rate = ANCHORS[config]
    n = int(np.prod(list(axis_sizes.values())))
    replicas = axis_sizes.get("data", 1)
    return per_replica * replicas / (n * rate)


def scaling_report(n_list=(8, 16, 64), configs=("resnet50",
                                                "transformer",
                                                "transformer_dp",
                                                "deepfm")) -> Dict:
    """The full report. Requires len(jax.devices()) >= max(n_list)
    (run under --xla_force_host_platform_device_count=64 on CPU)."""
    import jax
    from . import collective_audit as ca

    devices = jax.devices()
    if len(devices) < max(n_list):
        raise RuntimeError(
            f"scaling_report needs {max(n_list)} devices, "
            f"have {len(devices)}")
    builders = {"resnet50": _config_resnet,
                "transformer": _config_transformer,
                "transformer_dp": _config_transformer_dp,
                "deepfm": _config_deepfm}
    report: Dict = {"model": "ring-ICI analytic (see scaling_model.py)",
                    "ici_bw_B_per_s": ICI_BW, "ici_lat_s": ICI_LAT,
                    "anchors_measured": {k: v[2]
                                          for k, v in ANCHORS.items()},
                    "configs": {}}
    for cfg in configs:
        per_n = {}
        for n in n_list:
            hlo, mesh, axis_sizes = builders[cfg](n, devices)
            inv = ca.inventory(hlo, mesh)
            unattributed = [k for (k, axes) in inv if "?" in axes]
            assert not unattributed, (cfg, n, unattributed)
            pred = predict(inv, axis_sizes, _t_comp(cfg, axis_sizes))
            pred["mesh"] = axis_sizes
            # +-2x ICI-bandwidth sensitivity band: the one constant a
            # single-chip environment cannot measure. If the efficiency
            # conclusion survives bw/2, it does not hinge on the 45 GB/s
            # assumption.
            pred["sensitivity"] = {}
            for label, scale in (("bw_x0.5", 0.5), ("bw_x2.0", 2.0)):
                sp = predict(inv, axis_sizes, _t_comp(cfg, axis_sizes),
                             bw=ICI_BW * scale)
                pred["sensitivity"][label] = {
                    "eff_serial": sp["eff_serial"],
                    "eff_overlap": sp["eff_overlap"],
                    "t_comm_ms": sp["t_comm_ms"]}
            pred["inventory"] = {
                f"{kind} over {'+'.join(axes)}": [cnt, b]
                for (kind, axes), (cnt, b) in sorted(
                    inv.items(), key=lambda kv: -kv[1][1])}
            # multi-host view of the same compiled inventory: n chips
            # as H hosts x n/H chips (v5e-8 hosts), data axis over DCN
            hosts = {16: 2, 64: 8}.get(n)
            if hosts and axis_sizes.get("data", 1) % hosts == 0:
                pred["multihost"] = predict_multihost(
                    inv, axis_sizes, _t_comp(cfg, axis_sizes), hosts)
            per_n[str(n)] = pred
        lo, hi = str(min(n_list)), str(max(n_list))
        per_n["eff_%s_to_%s" % (lo, hi)] = round(
            per_n[hi]["eff_serial"] / per_n[lo]["eff_serial"], 4)
        report["configs"][cfg] = per_n
    return report


def deepfm_sparse_audit(n: int = 64) -> Dict:
    """EP-at-pod-scale evidence: the
    cross-chip bytes of the sharded-embedding lookup must scale with
    TOUCHED ROWS (batch x fields x embed_dim), not with table size —
    the property that makes the pserver-replacement viable. Verified
    by compiling the same DeepFM layout at 64 devices with a 1e5-row
    and a 4e5-row table and asserting the model-axis collective bytes
    are identical."""
    import jax
    from . import collective_audit as ca

    devices = jax.devices()
    out = {}
    for vocab in (int(1e5), int(4e5)):
        hlo, mesh, axis_sizes = _config_deepfm(n, devices,
                                               num_features=vocab)
        inv = ca.inventory(hlo, mesh)
        ca.assert_collectives(inv, [
            (("all-reduce", "reduce-scatter"), "data"),
            (("all-reduce",), "model"),   # the lookup's psum assembly
        ])
        out[vocab] = ca.axis_bytes(inv)
    b1, b4 = out[int(1e5)]["model"], out[int(4e5)]["model"]
    assert b1 == b4, (
        f"model-axis collective bytes changed with table size "
        f"({b1} vs {b4}) — sparse path is moving table-sized data")
    return {"n_devices": n, "model_axis_bytes_vocab_1e5": b1,
            "model_axis_bytes_vocab_4e5": b4,
            "scales_with_touched_rows": True}


def main(n_list=(8, 16, 64), configs=("resnet50", "transformer",
                                      "transformer_dp", "deepfm"),
         out_path="SCALING.json") -> None:
    report = scaling_report(n_list=n_list, configs=configs)
    audit = deepfm_sparse_audit(max(n_list))
    print("deepfm sparse audit (64 devices): model-axis bytes "
          f"{audit['model_axis_bytes_vocab_1e5']} (vocab 1e5) == "
          f"{audit['model_axis_bytes_vocab_4e5']} (vocab 4e5): "
          "gather traffic scales with touched rows, not table size")
    for cfg, per_n in report["configs"].items():
        for n, pred in per_n.items():
            if not n.isdigit():
                continue
            print(f"  scaling {cfg:12s} n={n:>3s} mesh={pred['mesh']} "
                  f"comp={pred['t_comp_ms']:.2f}ms "
                  f"comm={pred['t_comm_ms']:.2f}ms "
                  f"eff={pred['eff_serial']:.3f}"
                  f"/{pred['eff_overlap']:.3f} (serial/overlap)")
    lo, hi = str(min(n_list)), str(max(n_list))
    ratio_key = f"eff_{lo}_to_{hi}"
    summary = {cfg: {f"eff_serial_{hi}": per_n[hi]["eff_serial"],
                     ratio_key: per_n[ratio_key]}
               for cfg, per_n in report["configs"].items()}
    print("scaling-model summary: " + json.dumps(summary))
    if out_path:
        with open(out_path, "w") as fh:
            json.dump({"report": report, "deepfm_sparse_audit": audit},
                      fh, indent=1)
        print(f"scaling-model report written to {out_path}")


if __name__ == "__main__":
    main()
