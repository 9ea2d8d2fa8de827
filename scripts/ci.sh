#!/usr/bin/env bash
# CI entry point: tier-1 tests + docs check + example/bench smoke runs.
#
#   scripts/ci.sh            # full tier-1 + smoke
#   scripts/ci.sh --fast     # tier-1 + docs check only
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== tier-1: pytest =="
# pythonpath comes from pyproject.toml [tool.pytest.ini_options]
python -m pytest -x -q

echo "== docs: DESIGN.md section cross-references =="
python scripts/check_docs.py

if [[ "${1:-}" != "--fast" ]]; then
  echo "== parity: procedure-fused megakernel vs jnp backend =="
  python -m pytest -q \
    "tests/test_kernels.py::test_routing_procedure_fused_vs_jnp" \
    "tests/test_router.py::test_fusion_procedure_matches_jnp"

  echo "== grad parity: procedure custom VJP vs jnp autodiff =="
  python -m pytest -q \
    "tests/test_kernels.py::test_procedure_vjp_grad_parity" \
    "tests/test_router.py::test_differentiable_router_grad_matches_jnp" \
    "tests/test_router.py::test_capsnet_train_step_auto_plan_trains_fused"

  echo "== deep edge: int8 û streaming + early-exit (parity/property/errors) =="
  python -m pytest -q tests/test_quant.py \
    "tests/test_kernels.py::test_property_early_exit_eps0_bit_identical" \
    "tests/test_kernels.py::test_property_early_exit_monotone_work" \
    "tests/test_kernels.py::test_dma_model_int8_and_early_exit" \
    "tests/test_router.py::test_deep_edge_error_surface" \
    "tests/test_router.py::test_deep_edge_resolved_plan_roundtrip"

  echo "== smoke: examples/quickstart.py (Router API end-to-end) =="
  PYTHONPATH=src python examples/quickstart.py

  # Smoke benches run in a scratch cwd: benchmarks/run.py writes
  # BENCH_<name>.json to the current directory, and the repo-root copies
  # are the *tracked full-measurement* artifacts — a smoke run must never
  # clobber them.
  ROOT="$(pwd)"
  SMOKE_DIR="$(mktemp -d)"
  trap 'rm -rf "$SMOKE_DIR"' EXIT
  cd "$SMOKE_DIR"

  echo "== smoke: benchmarks.run --smoke --only rp_speedup (JSON artifact) =="
  PYTHONPATH="$ROOT/src:$ROOT" python -m benchmarks.run --smoke --only rp_speedup
  python - <<'EOF'
import json, sys
d = json.load(open("BENCH_rp_speedup.json"))
for key in ("bench", "smoke", "config", "provenance", "measured", "modeled",
            "geomean_modeled_speedup"):
    assert key in d, f"BENCH_rp_speedup.json missing {key!r}"
assert d["bench"] == "rp_speedup"
assert "kernel" in d["config"], "config missing kernel l_tile provenance"
arms = d["measured"]
assert arms, "no measured rows"
for row in arms:
    for arm in ("naive", "router_jnp", "sharded_fused", "procedure_fused",
                "procedure_fused_bf16", "procedure_fused_int8"):
        assert row[arm]["median_s"] > 0, (arm, row)
    # interpret-mode (CPU) pallas arms must be flagged modeled_only so
    # their wall-clock is never read as a hardware regression
    if d["provenance"]["pallas_interpret"]:
        for arm in ("sharded_fused", "procedure_fused",
                    "procedure_fused_bf16", "procedure_fused_int8"):
            assert row[arm]["modeled_only"] is True, (arm, row)
    dma = row["dma_model"]
    pf = dma["procedure_fused_fp32"]
    assert (2 * dma["procedure_fused_bf16"]["u_hat_stream_bytes"]
            == pf["u_hat_stream_bytes"]), dma
    # int8 quarters the û stream, leaves the fp32 b/v/s roundtrip alone
    assert (4 * dma["procedure_fused_int8"]["u_hat_stream_bytes"]
            == pf["u_hat_stream_bytes"]), dma
    assert (dma["procedure_fused_int8"]["roundtrip_bytes"]
            == pf["roundtrip_bytes"]), dma
    assert row["max_abs_delta_vs_jnp"]["procedure_fused"] <= 1e-5, row
    assert row["max_abs_delta_vs_jnp"]["procedure_fused_int8"] <= 0.1, row
    # measured early-exit ladder: monotone work, strictly below the fixed
    # grid at the top rung, and exactly the analytic freeze-after-it-1
    # floor there (min(iters, 2) iterations per tile)
    ee, iters = row["early_exit"], row["shape"]["iters"]
    effs = [r["effective_tile_iterations"] for r in ee["ladder"]]
    full = ee["full_tile_iterations"]
    assert full == iters * ee["n_l_tiles"], ee
    assert all(a >= b for a, b in zip(effs, effs[1:])), effs
    assert effs[-1] == min(iters, 2) * ee["n_l_tiles"], (effs, ee)
    assert effs[-1] < full, (effs, full)   # needs iters >= 3 in the shape
print("BENCH_rp_speedup.json OK:", len(arms), "measured row(s),",
      "sharded-fused + procedure-fused (fp32/bf16/int8) + early-exit",
      "ladder present")
EOF

  echo "== smoke: benchmarks.run --smoke --only accuracy (deep-edge gate) =="
  PYTHONPATH="$ROOT/src:$ROOT" python -m benchmarks.run --smoke --only accuracy
  python - <<'EOF'
import json

# STRICT loader: a NaN accuracy must fail CI, not serialize.
def _reject(name):
    raise AssertionError(f"non-finite constant {name} in BENCH_accuracy.json")

d = json.loads(open("BENCH_accuracy.json").read(), parse_constant=_reject)
for key in ("bench", "smoke", "config", "accuracy", "delta_vs_exact",
            "gate"):
    assert key in d, f"BENCH_accuracy.json missing {key!r}"
assert d["bench"] == "accuracy"
for mode in ("exact", "approx_no_recovery", "approx_with_recovery",
             "int8", "early_exit", "int8_early_exit"):
    assert 0.0 <= d["accuracy"][mode] <= 1.0, (mode, d["accuracy"])
g = d["gate"]
# the deep-edge accuracy gate (ROADMAP item 1): int8 / early-exit top-1
# within tol of exact fp32 — 0.5pt at the full eval, the 2-sample
# resolution floor under --smoke
assert g["tol"] == max(0.005, 2.0 / g["n_eval"]), g
for arm in ("int8", "early_exit", "int8_early_exit"):
    assert g[f"{arm}_pass"] is True, (arm, g)
    assert g[f"{arm}_delta"] <= g["tol"], (arm, g)
print("BENCH_accuracy.json OK (strict JSON): deep-edge gate",
      f"tol={g['tol']:.4f} on {g['n_eval']} samples,",
      f"int8 delta={g['int8_delta']:.4f},",
      f"early_exit delta={g['early_exit_delta']:.4f}")
EOF

  echo "== smoke: examples/train_capsnet.py --smoke --routing fused (custom VJP) =="
  PYTHONPATH="$ROOT/src" python "$ROOT/examples/train_capsnet.py" \
    --smoke --routing fused --ckpt-dir "$SMOKE_DIR/capsnet_ckpt"

  echo "== smoke: benchmarks.run --smoke --only train (JSON artifact) =="
  PYTHONPATH="$ROOT/src:$ROOT" python -m benchmarks.run --smoke --only train
  python - <<'EOF'
import json

# STRICT loader: a NaN loss or gradient delta must fail CI, not serialize.
def _reject(name):
    raise AssertionError(f"non-finite constant {name} in BENCH_train.json")

d = json.loads(open("BENCH_train.json").read(), parse_constant=_reject)
for key in ("bench", "smoke", "config", "provenance", "arms", "resolved",
            "grad_parity", "dma_model", "residual_model"):
    assert key in d, f"BENCH_train.json missing {key!r}"
assert d["bench"] == "train"

# the gate: fused backward must match jnp autodiff on the full param tree
gp = d["grad_parity"]
assert gp["fused_pass"] is True, gp
assert gp["bf16_pass"] is True, gp
assert gp["fused_max_abs_param_grad_delta"] <= gp["fused_tol"] == 1e-4, gp
assert gp["bf16_max_abs_param_grad_delta"] <= gp["bf16_tol"] == 2e-2, gp

for arm in ("jnp", "jnp_dp", "fused", "fused_bf16"):
    s = d["arms"][arm]
    assert s["median_s"] > 0, (arm, s)
    assert s["loss_decreased"] is True, (arm, s)
    # interpret-mode (CPU) pallas arms must be flagged modeled_only so
    # their wall-clock is never read as a hardware regression
    if arm.startswith("fused") and d["provenance"]["pallas_interpret"]:
        assert s["modeled_only"] is True, (arm, s)
assert d["resolved"]["fused"]["fusion"] == "procedure", d["resolved"]
assert d["resolved"]["fused"]["differentiable"] is True, d["resolved"]
assert d["resolved"]["jnp"]["differentiable"] is False, d["resolved"]

bwd = d["dma_model"]["backward_fp32"]
assert bwd["backward"] is True and bwd["total_bytes"] < bwd["naive_bytes"], bwd
rm = d["residual_model"]
assert rm["fused_residual_bytes"] < rm["unfused_residual_bytes"], rm
print("BENCH_train.json OK (strict JSON): grad-parity gate",
      f"fused={gp['fused_max_abs_param_grad_delta']:.2e}",
      f"bf16={gp['bf16_max_abs_param_grad_delta']:.2e},",
      len(d["arms"]), "arms, loss decreased in all")
EOF

  echo "== smoke: repro.launch.serve_caps --smoke (continuous batching) =="
  PYTHONPATH="$ROOT/src" python -m repro.launch.serve_caps --smoke

  echo "== smoke: repro.launch.serve_caps --smoke --async (threaded driver) =="
  PYTHONPATH="$ROOT/src" python -m repro.launch.serve_caps --smoke --async

  echo "== smoke: repro.launch.serve_caps --smoke --replicas 2 --tenants 2 (fleet) =="
  PYTHONPATH="$ROOT/src" python -m repro.launch.serve_caps --smoke \
    --replicas 2 --tenants 2 --slo-ms 5000

  echo "== smoke: repro.launch.serve_caps --smoke --chaos (fault injection) =="
  PYTHONPATH="$ROOT/src" python -m repro.launch.serve_caps --smoke --chaos

  echo "== smoke: repro.launch.serve_caps --smoke --chaos --replicas 2 (self-healing fleet) =="
  PYTHONPATH="$ROOT/src" python -m repro.launch.serve_caps --smoke --chaos \
    --replicas 2 --tenants 2 --slo-ms 5000

  echo "== smoke: repro.launch.serve_caps --smoke --model lm (WaveServe LM adapter) =="
  PYTHONPATH="$ROOT/src" python -m repro.launch.serve_caps --smoke --model lm

  echo "== smoke: repro.launch.serve_caps --smoke --model moe (WaveServe MoE adapter) =="
  PYTHONPATH="$ROOT/src" python -m repro.launch.serve_caps --smoke --model moe

  echo "== smoke: benchmarks.run --smoke --only serving (JSON artifact) =="
  PYTHONPATH="$ROOT/src:$ROOT" python -m benchmarks.run --smoke --only serving
  python - <<'EOF'
import json

# STRICT loader: NaN/Infinity are a regression (ServeMetrics.summary once
# emitted float("nan") percentiles), not valid JSON — reject them.
def _reject(name):
    raise AssertionError(f"non-finite constant {name} in BENCH_serving.json")

d = json.loads(open("BENCH_serving.json").read(), parse_constant=_reject)
for key in ("bench", "smoke", "config", "arms", "offered_loads",
            "outputs_identical", "max_abs_prob_delta",
            "em_outputs_identical", "em_max_abs_delta"):
    assert key in d, f"BENCH_serving.json missing {key!r}"
assert d["bench"] == "serving"
assert d["outputs_identical"], d["max_abs_prob_delta"]
assert d["em_outputs_identical"], d["em_max_abs_delta"]
assert len(d["offered_loads"]) >= 2, d["offered_loads"]
for arm in ("pipelined", "unpipelined", "async", "em_pipelined",
            "em_unpipelined"):
    cells = d["arms"][arm]
    assert len(cells) >= 2, (arm, cells)
    for c in cells:
        assert c["latency"]["median_s"] > 0, (arm, c)
        assert c["latency"]["p90_s"] > 0, (arm, c)
        assert c["throughput_rps"] > 0, (arm, c)
        assert c["shed"] == 0, (arm, c)

# fleet arm: tenants x offered-load sweep with goodput + per-tenant
# accounting (DESIGN.md §Fleet); the invariant must balance per tenant
assert "fleet" in d and d["fleet"]["replicas"] == 2, d.get("fleet")
assert 1.5 in d["fleet"]["offered_loads"], d["fleet"]
cells = d["arms"]["fleet"]
assert len(cells) == len(d["fleet"]["offered_loads"]), cells
for c in cells:
    assert c["latency"]["median_s"] > 0 and c["throughput_rps"] > 0, c
    assert isinstance(c["goodput"], int) and c["goodput"] > 0, c
    assert c["goodput"] <= c["requests"], c
    pt = c["per_tenant"]
    assert set(pt) == {"gold", "free"}, pt
    for name, t in pt.items():
        for k in ("submitted", "completed", "shed", "goodput", "pending"):
            assert k in t, (name, k, t)
        assert t["submitted"] == (t["completed"] + t["shed"] + t["failed"]
                                  + t["pending"]), (name, t)
    assert c["shed"] == sum(t["shed"] for t in pt.values()), c
    assert c["failed"] == 0 and c["wave_errors"] == 0, c  # fault-free arm

# chaos arm: the 1.0-load fleet cell under the injected fault schedule
# (DESIGN.md §Faults) — every fault fired, everything healed, nothing lost
assert "chaos" in d["arms"], sorted(d["arms"])
(cc,) = d["arms"]["chaos"]
assert cc["failed"] == 0, cc                      # zero lost requests
assert cc["wave_errors"] >= 3 and cc["retried"] >= 2, cc
assert cc["guard_trips"] >= 1, cc                 # NaN wave quarantined
assert cc["burials"] == 1, cc                     # replica crash healed
assert cc["evacuated"] == cc["adopted"] > 0, cc   # backlog re-dispatched
for name, t in cc["per_tenant"].items():
    assert t["submitted"] == (t["completed"] + t["shed"] + t["failed"]
                              + t["pending"]), (name, t)
    assert t["pending"] == 0, (name, t)

# mixed arm: CapsNet + LM decode + MoE waves through ONE CapsFleet
# (DESIGN.md §WaveServe) — per-workload goodput gates, nothing dropped
assert "mixed" in d["arms"], sorted(d["arms"])
(mx,) = d["arms"]["mixed"]
assert mx["failed"] == 0 and mx["shed"] == 0, mx
pw = mx["per_workload"]
assert set(pw) == {"caps", "lm", "moe"}, pw
for name, t in pw.items():
    assert t["completed"] == t["submitted"] > 0, (name, t)
    assert t["pending"] == 0, (name, t)
    assert t["goodput"] >= 0.8 * t["completed"], (name, t)
print("BENCH_serving.json OK (strict JSON):", len(d["arms"]), "arms x",
      len(d["offered_loads"]), "offered-load points + fleet sweep",
      d["fleet"]["offered_loads"], "+ chaos arm",
      {k: cc[k] for k in ("wave_errors", "retried", "guard_trips",
                          "burials")})
EOF
fi

echo "CI OK"
