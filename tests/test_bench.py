"""bench.py unit surface: the analytic MFU accounting (the measured part
runs on hardware via the driver)."""

import sys
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench

import pytest


pytestmark = pytest.mark.quick  # sub-2-min tier (tests/conftest.py)

def test_vgg11_flops_per_sample_matches_hand_count():
    """2 FLOPs/MAC x 3 passes x (conv MACs + fc): the 0.92 GFLOP/sample
    figure BENCH mfu is computed from."""
    got = bench.vgg11_train_flops_per_sample()
    # hand count: conv MACs per sample (SURVEY model spec, 32x32 input)
    macs = (32*32*3*64 + 16*16*64*128 + 8*8*128*256 + 8*8*256*256
            + 4*4*256*512 + 4*4*512*512 + 2*2*512*512 + 2*2*512*512) * 9
    macs += 512 * 10
    assert got == 2 * 3 * macs
    assert abs(got / 1e9 - 0.917) < 0.01  # the judge's estimate, confirmed


def test_peak_lookup():
    class Dev:
        def __init__(self, kind):
            self.device_kind = kind
    assert bench.peak_bf16_flops(Dev("TPU v5 lite0")) == 197.0e12
    assert bench.peak_bf16_flops(Dev("TPU v4")) == 275.0e12
    with pytest.raises(ValueError, match="no bf16 peak"):
        bench.peak_bf16_flops(Dev("cpu"))


def test_lm_flops_per_token_hand_count():
    """6P plus causal attention matmuls — the conservative denominator
    behind the lm_mfu bench key (round-4 transformer gates)."""
    cfg = bench._lm_cfg()
    n_params = 1_000_000
    got = bench.lm_train_flops_per_token(cfg, n_params, seq=2048)
    attn = 6 * 2048 * cfg.n_layers * cfg.n_heads * cfg.head_dim
    assert got == 6 * n_params + attn
    # the measurement config is the BASELINE one: byte-vocab d512/4L
    assert (cfg.vocab_size, cfg.d_model, cfg.n_layers) == (256, 512, 4)


def test_bench_json_keys_include_transformer_gates():
    """The driver-recorded JSON line must carry the round-4 gate keys
    (VERDICT round-3 #3) plus the round-6 hardened-window keys (p95
    companions and the overlap A/B) and the round-7 int8-KV keys (the
    kv_dtype knob, the per-step KV-bytes estimate, and the acceptance-
    adjusted serving utilization) — pin the schema without running
    hardware."""
    import inspect
    src = inspect.getsource(bench.main)
    for key in ("lm_tokens_per_sec_per_chip", "lm_mfu",
                "decode_ms_per_token", "decode_ms_per_token_p95",
                "serving_tokens_per_sec", "serving_tokens_per_sec_p95",
                "serving_tokens_per_sec_no_overlap",
                "serving_overlap_speedup",
                "serving_slot_step_utilization",
                "kv_dtype", "decode_kv_bytes_per_step",
                "serving_emitted_per_slot_step",
                # round-8 backward-overlap A/B keys
                "train_overlap_speedup", "train_step_ms_overlap",
                "train_step_ms_post_backward",
                # round-9 factored-mesh DCN A/B keys
                "train_dcn_overlap_speedup", "train_dcn_bytes_per_step",
                "train_dcn_compress",
                # round-16 low-bit keys
                "train_dcn_int4_bytes_per_step", "lm_q8_gather_speedup",
                "lm_int8_matmul_fliprate"):
        assert key in src, key
    # the knob reaches both inference gates
    assert "BENCH_KV_DTYPE" in src
    # the overlap knob is validated PRE-bench (canon_overlap_env), same
    # fail-loudly contract as BENCH_KV_DTYPE
    assert "canon_overlap_env" in src
    # the dcn knobs too (round 9): size and slow-hop compression both
    # canonicalized before any measurement
    assert "canon_dcn_size_env" in src and "BENCH_DCN_SIZE" in src
    assert "canon_dcn_compress_env" in src and "BENCH_DCN_COMPRESS" in src
    # round 16: the quantized-gather and int8-matmul gates follow the
    # same canonicalize-pre-bench contract
    assert "canon_fsdp_gather_env" in src and "BENCH_FSDP_GATHER" in src
    assert "canon_matmul_dtype_env" in src and "BENCH_MATMUL_DTYPE" in src


def test_bench_dcn_env_knobs_fail_loudly():
    """Typo'd BENCH_DCN_SIZE / BENCH_DCN_COMPRESS must raise before any
    measurement; unset/0/none skip cleanly."""
    assert bench.canon_dcn_size_env(None) == 0
    assert bench.canon_dcn_size_env("") == 0
    assert bench.canon_dcn_size_env("0") == 0
    assert bench.canon_dcn_size_env("2") == 2
    assert bench.canon_dcn_size_env("4") == 4
    for bad in ("1", "-2", "two", "2.5"):
        with pytest.raises(ValueError, match="BENCH_DCN_SIZE"):
            bench.canon_dcn_size_env(bad)
    assert bench.canon_dcn_compress_env(None) is None
    assert bench.canon_dcn_compress_env("") is None
    assert bench.canon_dcn_compress_env("none") is None
    assert bench.canon_dcn_compress_env("int8") == "int8"
    assert bench.canon_dcn_compress_env("int4") == "int4"
    for bad in ("fp8", "INT8", "1", "int2"):
        with pytest.raises(ValueError, match="BENCH_DCN_COMPRESS"):
            bench.canon_dcn_compress_env(bad)
    # round 16: the quantized-gather and int8-matmul knobs, same contract
    assert bench.canon_fsdp_gather_env(None) is None
    assert bench.canon_fsdp_gather_env("") is None
    assert bench.canon_fsdp_gather_env("none") is None
    assert bench.canon_fsdp_gather_env("int8") == "int8"
    for bad in ("int4", "fp8", "INT8"):
        with pytest.raises(ValueError, match="BENCH_FSDP_GATHER"):
            bench.canon_fsdp_gather_env(bad)
    assert bench.canon_matmul_dtype_env(None) is None
    assert bench.canon_matmul_dtype_env("") is None
    assert bench.canon_matmul_dtype_env("none") is None
    assert bench.canon_matmul_dtype_env("int8") == "int8"
    for bad in ("int4", "bf16", "INT8"):
        with pytest.raises(ValueError, match="BENCH_MATMUL_DTYPE"):
            bench.canon_matmul_dtype_env(bad)


def test_bench_train_dcn_uses_hardened_window_and_inspector():
    """The dcn A/B inherits the hardened-window discipline (>= 5
    alternating reps, median, precompile outside the window) and reads
    its byte columns from the per-axis schedule inspector rather than
    asserting them."""
    import inspect
    sig = inspect.signature(bench.bench_train_dcn)
    assert sig.parameters["reps"].default >= 5
    src = inspect.getsource(bench.bench_train_dcn)
    assert "hierarchical" in src and "precompile_steps" in src
    assert "per_axis_collective_stats" in src
    assert "dcn_compress=compress" in src


def test_bench_overlap_env_knob_fails_loudly():
    """A typo'd BENCH_OVERLAP must raise before any measurement, not be
    swallowed into a silently-skipped (or silently-run) A/B."""
    assert bench.canon_overlap_env(None) is True
    assert bench.canon_overlap_env("") is True
    assert bench.canon_overlap_env("1") is True
    assert bench.canon_overlap_env("0") is False
    for bad in ("yes", "true", "On", "2", " 1"):
        with pytest.raises(ValueError, match="BENCH_OVERLAP"):
            bench.canon_overlap_env(bad)


def test_bench_train_overlap_uses_hardened_window():
    """The overlap A/B inherits the hardened-window discipline: >= 5
    alternating reps, median-of-reps, value fetch as the step barrier,
    and the bitwise-pinned bucketed strategy on both sides."""
    import inspect
    sig = inspect.signature(bench.bench_train_overlap)
    assert sig.parameters["reps"].default >= 5
    src = inspect.getsource(bench.bench_train_overlap)
    assert "overlap=overlap" in src and "bucketed" in src
    assert "precompile_steps" in src  # compile excluded from timed reps


def test_bench_strategies_emits_comm_columns():
    """scripts/bench_strategies.py's JSON rows carry the wire-accounting
    columns (round 8): comm bytes + jaxpr/HLO collective counts from the
    schedule inspector, making BASELINE.md's strategy cost table
    reproducible from one command."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "bench_strategies.py")
    with open(path) as f:
        src = f.read()
    for key in ("comm_bytes_per_step", "collective_count",
                "collectives_interleaved", "hlo_collective_count",
                "op_schedule", "hlo_collective_counts",
                # round 9: per-axis (dcn vs ici) byte/count columns from
                # per_axis_collective_stats, plus the compressed-hop row
                "comm_bytes_by_axis", "collective_count_by_axis",
                "per_axis_collective_stats", "hierarchical_int8",
                # round 16: the half-width DCN row and the quantized
                # ZeRO-3 gather row
                "hierarchical_int4", "lm_fsdp_q8gather"):
        assert key in src, key


def test_bench_decode_kv_dtype_knob_and_bytes_estimate():
    """The decode gate accepts kv_dtype and its analytic KV-bytes
    estimate halves (modulo the scale overhead) from bf16 to int8 —
    the predicted HBM effect the JSON carries next to the measured
    ms/token."""
    import inspect
    import jax.numpy as jnp
    from distributed_pytorch_tpu import generate as gen
    sig = inspect.signature(bench.bench_decode)
    assert "kv_dtype" in sig.parameters
    assert "kv_dtype" in inspect.signature(
        bench.bench_serving).parameters
    cfg = bench._lm_cfg()
    bf16 = gen.kv_bytes_per_token(cfg, dtype=jnp.bfloat16)
    int8 = gen.kv_bytes_per_token(cfg, kv_dtype="int8")
    assert 1.9 <= bf16 / int8 <= 2.0
    # the estimate in bench_decode is B x mean_len x per-token bytes
    src = inspect.getsource(bench.bench_decode)
    assert "kv_bytes_per_token" in src


def test_bench_decode_uses_hardened_window():
    """The decode gate's defects were the round-5 red flag (VERDICT r5
    #1): whole-wall/max_new denominator (prefill included) ended by a
    full-output fetch.  Pin the hardened shape: paired windows,
    one-element fetch, median of >= 5 reps."""
    import inspect
    sig = inspect.signature(bench.bench_decode)
    assert sig.parameters["reps"].default >= 5
    assert sig.parameters["base"].default >= 1
    src = inspect.getsource(bench.bench_decode)
    assert "force_fetch_last" in src
    assert "np.asarray(out)" not in src


def test_bench_autotune_env_knob_fails_loudly():
    """A typo'd BENCH_AUTOTUNE must raise before any measurement (the
    BENCH_KV_DTYPE contract); unset/''/'0' skip cleanly, '1' runs."""
    assert bench.canon_autotune_env(None) is False
    assert bench.canon_autotune_env("") is False
    assert bench.canon_autotune_env("0") is False
    assert bench.canon_autotune_env("1") is True
    for bad in ("yes", "true", "2", " 1", "auto"):
        with pytest.raises(ValueError, match="BENCH_AUTOTUNE"):
            bench.canon_autotune_env(bad)


def test_bench_json_keys_include_autotune_gate():
    """Round-11 schema: the autotune A/B keys ride the JSON, the knob is
    canonicalized pre-bench, and the leg calibrates -> chooses -> A/Bs
    with the hardened-window discipline (>= 5 alternating reps, median,
    precompile outside the window) against the hand-picked default."""
    import inspect
    src = inspect.getsource(bench.main)
    for key in ("train_autotune_speedup", "train_autotune_plan"):
        assert key in src, key
    assert "canon_autotune_env" in src and "BENCH_AUTOTUNE" in src
    sig = inspect.signature(bench.bench_train_autotune)
    assert sig.parameters["reps"].default >= 5
    atsrc = inspect.getsource(bench.bench_train_autotune)
    assert "get_profile" in atsrc          # calibrate-or-cache
    assert "precompile_steps" in atsrc     # compile outside the window
    assert "plan.summary()" in atsrc       # the explainable plan rides
    assert 'strategy="auto" if auto else "ddp"' in atsrc  # the A/B pair


def test_bench_strategies_emits_predicted_ms_and_auto_row():
    """scripts/bench_strategies.py (round 11): every row gains the cost
    model's predicted_ms next to the measured per-axis byte columns,
    and an 'auto' row resolves from a CPU-calibrated profile."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "bench_strategies.py")
    with open(path) as f:
        src = f.read()
    for key in ("predicted_ms", "autotune.calibrate", "predict_named",
                '"auto"', "resolved"):
        assert key in src, key


def test_bench_elastic_env_knob_fails_loudly():
    """A typo'd BENCH_ELASTIC must raise before any measurement (the
    BENCH_KV_DTYPE contract); unset/''/'0' skip cleanly, '1' runs."""
    assert bench.canon_elastic_env(None) is False
    assert bench.canon_elastic_env("") is False
    assert bench.canon_elastic_env("0") is False
    assert bench.canon_elastic_env("1") is True
    for bad in ("yes", "true", "2", " 1", "elastic"):
        with pytest.raises(ValueError, match="BENCH_ELASTIC"):
            bench.canon_elastic_env(bad)


def test_bench_json_keys_include_elastic_gate():
    """Round-12 schema: the elastic-recovery keys ride the JSON, the
    knob is canonicalized pre-bench, and the gate's recovery leg goes
    through the real resize machinery — trainer rebuild + the
    cross-topology reshard loader — on a SHARDED checkpoint, with a
    proving step inside the timed window."""
    import inspect
    src = inspect.getsource(bench.main)
    for key in ("elastic_recovery_ms", "elastic_resize_events"):
        assert key in src, key
    assert "canon_elastic_env" in src and "BENCH_ELASTIC" in src
    esrc = inspect.getsource(bench.bench_elastic)
    assert "reshard_from_checkpoint" in esrc  # rebuild + load_resharded
    assert "ShardedCheckpointer" in esrc
    assert "train_step" in esrc               # the proving step is timed


def test_bench_telemetry_env_knob_fails_loudly():
    """A typo'd BENCH_TELEMETRY must raise before any measurement (the
    BENCH_KV_DTYPE contract, via the ONE shared _canon_bool_env);
    unset/''/'0' skip cleanly, '1' runs."""
    assert bench.canon_telemetry_env(None) is False
    assert bench.canon_telemetry_env("") is False
    assert bench.canon_telemetry_env("0") is False
    assert bench.canon_telemetry_env("1") is True
    for bad in ("yes", "true", "2", " 1", "on"):
        with pytest.raises(ValueError, match="BENCH_TELEMETRY"):
            bench.canon_telemetry_env(bad)


def test_bench_json_keys_include_telemetry_gate():
    """Round-13 schema: the telemetry-overhead keys ride the JSON, the
    knob is canonicalized pre-bench, and the A/B follows the
    hardened-window discipline (>= 5 alternating reps, median,
    precompile outside the window) with the registry toggled in-session
    around the SAME trainer (identical compiled programs)."""
    import inspect
    src = inspect.getsource(bench.main)
    for key in ("telemetry_overhead_pct", "train_step_ms_telemetry_on",
                "train_step_ms_telemetry_off"):
        assert key in src, key
    assert "canon_telemetry_env" in src and "BENCH_TELEMETRY" in src
    sig = inspect.signature(bench.bench_train_telemetry)
    assert sig.parameters["reps"].default >= 5
    tsrc = inspect.getsource(bench.bench_train_telemetry)
    assert "precompile_steps" in tsrc   # compile outside the window
    assert "telemetry.enable" in tsrc and "telemetry.disable" in tsrc
    assert "for on in (False, True)" in tsrc  # alternating A/B


def test_bench_fleet_env_knob_fails_loudly():
    """A typo'd BENCH_FLEET must raise before any measurement (the
    BENCH_KV_DTYPE contract, via the ONE shared _canon_bool_env);
    unset/''/'0' skip cleanly, '1' runs."""
    assert bench.canon_fleet_env(None) is False
    assert bench.canon_fleet_env("") is False
    assert bench.canon_fleet_env("0") is False
    assert bench.canon_fleet_env("1") is True
    for bad in ("yes", "true", "2", " 1", "on"):
        with pytest.raises(ValueError, match="BENCH_FLEET"):
            bench.canon_fleet_env(bad)


def test_bench_json_keys_include_fleet_gate():
    """Round-14 schema: the serving-fleet keys ride the JSON, the knob
    is canonicalized pre-bench, and the gate measures a warm fleet
    (compiled fns shared per replica via warm_clone) with a
    disaggregated pass for the handoff cost."""
    import inspect
    src = inspect.getsource(bench.main)
    for key in ("fleet_tokens_per_sec", "fleet_prefix_hit_rate",
                "fleet_handoff_ms"):
        assert key in src, key
    assert "canon_fleet_env" in src and "BENCH_FLEET" in src
    fsrc = inspect.getsource(bench.bench_serve_fleet)
    assert "warm_clone" in fsrc           # timed fleets run warm
    assert "make_fleet" in fsrc
    assert "disaggregate=True" in fsrc    # the handoff pass is real
    sig = inspect.signature(bench.bench_serve_fleet)
    assert sig.parameters["reps"].default >= 3  # hardened window


def test_bench_fleet_transport_env_knob_fails_loudly():
    """A typo'd BENCH_FLEET_TRANSPORT must raise before any measurement
    (the shared _canon_bool_env contract); unset/''/'0' skip cleanly,
    '1' runs."""
    assert bench.canon_fleet_transport_env(None) is False
    assert bench.canon_fleet_transport_env("") is False
    assert bench.canon_fleet_transport_env("0") is False
    assert bench.canon_fleet_transport_env("1") is True
    for bad in ("yes", "true", "2", " 1", "on"):
        with pytest.raises(ValueError, match="BENCH_FLEET_TRANSPORT"):
            bench.canon_fleet_transport_env(bad)


def test_bench_json_keys_include_fleet_transport_gate():
    """Round-19 schema: the multi-process transport keys ride the JSON,
    the knob is canonicalized pre-bench, and the gate prices a REAL
    socket fleet (daemons pinned off the parent's accelerator) plus an
    autoscaler spawn->drain cycle."""
    import inspect
    src = inspect.getsource(bench.main)
    for key in ("fleet_rpc_overhead_ms", "fleet_autoscale_events"):
        assert key in src, key
    assert "canon_fleet_transport_env" in src
    assert "BENCH_FLEET_TRANSPORT" in src
    tsrc = inspect.getsource(bench.bench_fleet_transport)
    assert "make_socket_fleet" in tsrc    # real daemons, real sockets
    assert "JAX_PLATFORMS" in tsrc        # daemons must not grab the TPU
    assert "FleetAutoscaler" in tsrc
    assert "rpc_overhead_ms" in tsrc


def test_bench_meta_block_schema():
    """Round-15 schema: every bench JSON carries a provenance meta block
    (git sha, jax/jaxlib versions, platform, device kind, hostname, UTC
    timestamp) so bench_compare.py can refuse cross-host gating."""
    import inspect
    src = inspect.getsource(bench.bench_meta)
    for key in ("git_sha", "jax_version", "jaxlib_version", "platform",
                "device_kind", "device_count", "hostname", "python",
                "timestamp_utc"):
        assert key in src, key
    assert '"meta": bench_meta()' in inspect.getsource(bench.main)
    meta = bench.bench_meta()
    assert set(meta) >= {"git_sha", "jax_version", "platform",
                         "device_kind", "hostname", "timestamp_utc"}
    assert meta["platform"]  # a live backend answered
    assert meta["timestamp_utc"].endswith("Z")
    import json
    json.dumps(meta)  # JSON-serializable as emitted


def _compare_mod():
    import importlib
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    try:
        return importlib.import_module("bench_compare")
    finally:
        sys.path.pop(0)


def _bench_json(tmp_path, name, metrics, *, meta=None, wrap=False):
    import json
    data = {"metric": "images_per_sec_per_chip", **metrics}
    if meta is not None:
        data["meta"] = meta
    if wrap:  # the driver's BENCH_r*.json wrapper
        data = {"n": 1, "cmd": "bench", "rc": 0, "tail": "",
                "parsed": data}
    path = tmp_path / name
    path.write_text(json.dumps(data))
    return str(path)


def test_bench_compare_detects_regressions_and_unwraps(tmp_path, capsys):
    """The perf gate: a throughput drop / latency rise beyond tolerance
    exits 1; within-tolerance noise and improvements pass — and the
    driver's BENCH_r*.json wrapper is unwrapped transparently."""
    bc = _compare_mod()
    old = _bench_json(tmp_path, "old.json",
                      {"value": 100.0, "mfu": 0.30,
                       "decode_ms_per_token": 10.0,
                       "telemetry_overhead_pct": -0.15}, wrap=True)
    ok = _bench_json(tmp_path, "ok.json",
                     {"value": 95.0, "mfu": 0.31,
                      "decode_ms_per_token": 10.5,
                      "telemetry_overhead_pct": 0.4})
    bad = _bench_json(tmp_path, "bad.json",
                      {"value": 80.0, "mfu": 0.31,
                       "decode_ms_per_token": 13.0,
                       "telemetry_overhead_pct": 3.5})
    assert bc.main([old, ok]) == 0
    capsys.readouterr()
    assert bc.main([old, bad]) == 1
    out = capsys.readouterr().out
    # value -20% (>10% drop), decode +30% (>15% rise), overhead > 2.0
    assert out.count("REGRESSED") == 3
    assert "value" in out and "decode_ms_per_token" in out
    # keys absent from either side are skipped, not judged
    assert "fleet_tokens_per_sec" not in out
    # trajectory mode: consecutive pairs, any regression gates
    assert bc.main(["--trajectory", old, ok, bad]) == 1


def test_bench_compare_meta_gating(tmp_path, capsys):
    """A platform/device change makes results incomparable: regressions
    are reported but NOT gated unless --across-hosts; legacy JSONs
    without meta compare unconditionally."""
    bc = _compare_mod()
    cpu = {"platform": "cpu", "device_kind": "cpu", "hostname": "a"}
    tpu = {"platform": "tpu", "device_kind": "TPU v5 lite", "hostname": "b"}
    old = _bench_json(tmp_path, "o.json", {"value": 100.0}, meta=tpu)
    new = _bench_json(tmp_path, "n.json", {"value": 10.0}, meta=cpu)
    assert bc.main([old, new]) == 0  # host changed: not a regression
    assert "NOT gated" in capsys.readouterr().out
    assert bc.main([old, new, "--across-hosts"]) == 1  # forced gate
    capsys.readouterr()
    # same host: gated normally
    new_same = _bench_json(tmp_path, "ns.json", {"value": 10.0}, meta=tpu)
    assert bc.main([old, new_same]) == 1
    capsys.readouterr()
    # legacy (no meta): gated normally
    old_legacy = _bench_json(tmp_path, "ol.json", {"value": 100.0})
    assert bc.main([old_legacy, new]) == 1
    capsys.readouterr()
    # a non-bench JSON fails loudly, not silently-passes
    junk = tmp_path / "junk.json"
    junk.write_text("{}")
    with pytest.raises(ValueError, match="not a bench JSON"):
        bc.main([old, str(junk)])


def test_bench_compare_rule_table_covers_baseline_keys():
    """Every gated BASELINE.md figure has a rule with the right
    direction: throughput/MFU/speedups up, latencies down, the
    telemetry overhead held to its round-13 acceptance ceiling."""
    bc = _compare_mod()
    for key in ("value", "mfu", "lm_tokens_per_sec_per_chip", "lm_mfu",
                "serving_tokens_per_sec", "train_overlap_speedup",
                "train_dcn_overlap_speedup", "train_autotune_speedup",
                "serving_overlap_speedup",
                "fleet_tokens_per_sec", "fleet_prefix_hit_rate"):
        assert bc.RULES[key][0] == "higher", key
    for key in ("decode_ms_per_token", "decode_ms_per_token_p95",
                "elastic_recovery_ms", "fleet_handoff_ms",
                "fleet_rpc_overhead_ms"):
        assert bc.RULES[key][0] == "lower", key
    assert bc.ABS_CEILINGS["telemetry_overhead_pct"] == 2.0
    # round-19: one framed RPC round-trip must stay decisively under a
    # decode step regardless of the old run's value
    assert bc.ABS_CEILINGS["fleet_rpc_overhead_ms"] == 5.0
