import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# Compiles on forced CPU host devices; never takes an accelerator.
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: prove the distribution config is coherent.

For one (arch × input-shape × mesh) combination this script:
  1. builds the production mesh ((16,16) or (2,16,16) = 512 placeholder
     host devices — hence the XLA_FLAGS and JAX_PLATFORMS lines ABOVE
     ALL OTHER IMPORTS),
  2. lowers + COMPILES the appropriate step (train_step for train_4k,
     prefill for prefill_32k, serve_step for decode shapes) with full
     production shardings over ShapeDtypeStructs (no allocation),
  3. prints memory_analysis() (fits-on-chip proof) and cost_analysis()
     (FLOPs/bytes for §Roofline), and parses the compiled HLO for the
     collective schedule,
  4. writes a JSON record consumed by launch/report.py -> EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch gemma-7b --shape train_4k \
      [--multi-pod] [--strategy rhd_rsa] [--json out.json]
  python -m repro.launch.dryrun --all [--multi-pod]   # loops in-process
"""
import argparse
import json
import sys
import time
import traceback


def _build_step(arch: str, shape_name: str, mesh, strategy: str,
                fusion_mb: float, sharding_aware: bool = True,
                remat: bool = False, wire_dtype: str = "",
                spec_overrides=None, selector_mode: str = "analytic",
                selector_table: str = "", overlap: bool = False,
                codec: str = "", error_feedback: bool = False,
                legacy_partial_auto: bool = False):
    """Returns (jitted_fn, arg_structs, aux); aux carries the
    GradientAggregator (train shapes only) so the caller can report the
    resolved per-bucket schedule."""
    import dataclasses

    import jax
    from repro.configs import SHAPES, get_spec, input_specs, spec_for_shape
    from repro.core import AggregatorConfig
    from repro.launch.mesh import dp_axes_of
    from repro.models import build_model
    from repro.optim import adamw, cosine_warmup
    from repro.serve.step import make_decode_step, make_prefill_step
    from repro.train import TrainStepConfig, make_train_step

    spec = spec_for_shape(get_spec(arch), shape_name)
    if remat:
        spec = dataclasses.replace(spec, remat=True)
    if spec_overrides:
        spec = dataclasses.replace(spec, **spec_overrides)
    shape = SHAPES[shape_name]
    model = build_model(spec)
    dp_axes = dp_axes_of(mesh)
    specs = input_specs(spec, shape_name)

    if shape.kind == "train":
        opt = adamw(cosine_warmup(3e-4, 100, 10000))
        cfg = TrainStepConfig(
            aggregator=AggregatorConfig(strategy=strategy,
                                        fusion_threshold_mb=fusion_mb,
                                        sharding_aware=sharding_aware,
                                        wire_dtype=wire_dtype,
                                        selector_mode=selector_mode,
                                        selector_table=selector_table,
                                        overlap=overlap,
                                        codec=codec,
                                        error_feedback=error_feedback),
            dp_axes=dp_axes)
        step, shardings = make_train_step(
            model, opt, mesh, cfg, specs, donate=False,
            legacy_partial_auto=legacy_partial_auto)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        opt_state = jax.eval_shape(opt.init, params)
        agg = shardings.get("aggregator")
        aux = {"aggregator": agg, "dp_axes": dp_axes,
               "resolve_struct": params, "model_axis_size": None}
        if agg is not None and getattr(agg, "model_axis", None):
            # Full-manual lowering (§3.12): the aggregator sees SHARD-
            # shaped grads inside the region, so the preview resolve
            # must run on the sharded structs with the static axis size.
            from repro.core import manual as manual_mod
            m = int(mesh.shape.get(agg.model_axis, 1))
            mspecs = manual_mod.model_shard_specs(params, mesh,
                                                  axis=agg.model_axis)
            aux["resolve_struct"] = manual_mod.shard_param_structs(
                params, mspecs, m)
            aux["model_axis_size"] = m
        return step, (params, opt_state, specs), aux

    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if shape.kind == "prefill":
        step = make_prefill_step(model, mesh, dp_axes, specs,
                                 max_seq=shape.seq_len)
        return step, (params, specs), {}

    # decode
    step = make_decode_step(model, mesh, dp_axes, shape.global_batch,
                            shape.seq_len, donate=False)
    return step, (params, specs["cache"], specs["tokens"]), {}


def _schedule_record(agg, mesh, dp_axes, params_struct, roof,
                     collective_bytes=None,
                     model_axis_size=None) -> dict:
    """Resolve and record the ReduceSchedule IR (DESIGN.md §3.8): the
    same object the compiled step executes — per-bucket decomposition
    trees with per-stage wire bytes and latencies — serialized under
    schema repro/schedule/v1, plus the roofline-charged comm latency,
    the IR-vs-HLO wire-byte cross-check, and the overlap timeline
    (bucket ready-times played against per-bucket latencies to predict
    how much of the comm the backward hides, core/overlap.py)."""
    from repro.analysis import verify as analysis_verify
    from repro.core import overlap as overlap_mod
    from repro.launch import roofline as rl
    from repro.models import param_groups

    axis_sizes = tuple(int(mesh.shape[a]) for a in dp_axes)
    sched = agg.resolve(params_struct, axis_sizes,
                        groups=param_groups(params_struct),
                        model_axis_size=model_axis_size)
    timeline = overlap_mod.simulate_schedule(sched,
                                             compute_s=roof.compute_s)
    verify_diags = analysis_verify.verify_schedule(sched)
    return {
        "axis_sizes": list(axis_sizes),
        "verify": {
            "n_errors": sum(d.severity == "error" for d in verify_diags),
            "n_warnings": sum(d.severity == "warn"
                              for d in verify_diags),
            "diagnostics": [d.to_json() for d in verify_diags],
        },
        "n_buckets": sched.n_buckets,
        "algorithms": sched.algorithms(),
        "decomposition": sched.render(),
        "predicted_comm_s": sched.predicted_s,
        "charged_comm_s": roof.collective_s,
        "wire_check": rl.wire_check(sched, collective_bytes or {}),
        "overlap": rl.overlap_report(roof, timeline),
        # the serialized IR itself — launch/report.py renders its
        # decomposition column straight from this record.  Grouped so
        # --all sweeps over many-bucket configs stay readable (runs of
        # identical buckets collapse; readiness ranks are preserved)
        "ir": sched.to_json(group=True),
    }


def _attach_trace(rec: dict, arch: str, shape_name: str, mesh,
                  strategy: str, fusion_mb: float, sharding_aware: bool,
                  remat: bool, wire_dtype: str, spec_overrides,
                  selector_mode: str, selector_table: str, overlap: bool,
                  codec: str, error_feedback: bool, trace_path: str,
                  verbose: bool = True,
                  legacy_partial_auto: bool = False) -> None:
    """--trace: enable telemetry, replay the config's ReduceSchedule
    through the measured probe (repro.telemetry.closure — each distinct
    stage as its own jitted collective on an axis_size submesh of the
    dry-run's forced host devices), attach the per-stage residual table
    + metrics snapshot to the record and write the Perfetto trace."""
    import dataclasses

    import jax
    from repro import telemetry
    from repro.configs import SHAPES, get_spec, spec_for_shape
    from repro.core import AggregatorConfig, GradientAggregator
    from repro.launch.mesh import dp_axes_of
    from repro.models import build_model, param_groups
    from repro.telemetry import closure

    if SHAPES[shape_name].kind != "train":
        rec["measured"] = {"skipped":
                           "no ReduceSchedule on non-train shapes"}
        return
    tracer = telemetry.configure(telemetry.TelemetryConfig(enabled=True))
    spec = spec_for_shape(get_spec(arch), shape_name)
    if remat:
        spec = dataclasses.replace(spec, remat=True)
    if spec_overrides:
        spec = dataclasses.replace(spec, **spec_overrides)
    model = build_model(spec)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    dp_axes = dp_axes_of(mesh)
    # mirror make_train_step's lowering gate so the replayed schedule is
    # the one the compiled step carries (bracketed under full-manual)
    manual = ("model" in mesh.axis_names and not legacy_partial_auto
              and not bool(getattr(spec, "seq_parallel", False)))
    agg = GradientAggregator(
        AggregatorConfig(strategy=strategy, fusion_threshold_mb=fusion_mb,
                         sharding_aware=sharding_aware,
                         wire_dtype=wire_dtype,
                         selector_mode=selector_mode,
                         selector_table=selector_table,
                         overlap=overlap, codec=codec,
                         error_feedback=error_feedback), dp_axes,
        model_axis="model" if manual else None)
    axis_sizes = tuple(int(mesh.shape[a]) for a in dp_axes)
    model_m = None
    if manual:
        from repro.core import manual as manual_mod
        model_m = int(mesh.shape.get("model", 1))
        mspecs = manual_mod.model_shard_specs(params, mesh)
        params = manual_mod.shard_param_structs(params, mspecs, model_m)
    with tracer.span("dryrun.trace", cat="wall", arch=arch,
                     shape=shape_name):
        sched = agg.resolve(params, axis_sizes,
                            groups=param_groups(params),
                            model_axis_size=model_m)
        measured = closure.measure_schedule(sched, reps=2, tracer=tracer)
        report = closure.closure_report(sched, measured)
    rec["measured"] = report
    if rec.get("schedule") and rec.get("roofline", {}).get("compute_s"):
        # OK records carry a roofline: replay the §3.6 simulator with
        # the measured per-bucket latencies (calibrated back into model
        # units) so report.py can put a measured overlap fraction next
        # to the predicted one.
        tl = closure.measured_timeline(
            sched, measured, report["calibration"]["k"],
            compute_s=float(rec["roofline"]["compute_s"]))
        rec["schedule"]["measured_overlap"] = {
            "overlap_fraction": tl.overlap_fraction,
            "hidden_comm_s": tl.hidden_comm_s,
            "exposed_comm_s": tl.exposed_comm_s,
            "step_s": tl.step_s,
        }
    rec["metrics"] = telemetry.METRICS.snapshot()
    tracer.write(trace_path)
    if verbose:
        cal = report["calibration"]
        print(f"  trace: {report['n_stages']} stages "
              f"({report['n_gated']} gated) k={cal['k']:.3g} "
              f"max_ratio={report['max_ratio']:.2f} "
              f"within_band={report['all_within_band']} -> {trace_path}")


def run_one(arch: str, shape_name: str, multi_pod: bool,
            strategy: str = "rhd_rsa", fusion_mb: float = 4.0,
            sharding_aware: bool = True, verbose: bool = True,
            remat: bool = False, wire_dtype: str = "",
            spec_overrides=None, selector_mode: str = "analytic",
            selector_table: str = "", overlap: bool = False,
            codec: str = "", error_feedback: bool = False,
            trace_path: str = "",
            legacy_partial_auto: bool = False) -> dict:
    import jax
    from repro.configs import SHAPES, get_spec, shape_supported
    from repro.launch import roofline as rl
    from repro.launch.mesh import make_production_mesh

    spec = get_spec(arch)
    ok, why = shape_supported(spec, shape_name)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "strategy": strategy, "fusion_mb": fusion_mb,
           "sharding_aware": sharding_aware, "remat": remat,
           "wire_dtype": wire_dtype, "overlap": overlap,
           "codec": codec or "none", "error_feedback": error_feedback,
           "spec_overrides": spec_overrides or {}}
    if not ok:
        rec.update(status="SKIP", reason=why)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 512 if multi_pod else 256
    t0 = time.perf_counter()
    try:
        # context mesh so bare-P sharding constraints resolve
        with jax.set_mesh(mesh):
            step, args, aux = _build_step(arch, shape_name, mesh, strategy,
                                          fusion_mb, sharding_aware,
                                          remat=remat,
                                          wire_dtype=wire_dtype,
                                          spec_overrides=spec_overrides,
                                          selector_mode=selector_mode,
                                          selector_table=selector_table,
                                          overlap=overlap, codec=codec,
                                          error_feedback=error_feedback,
                                          legacy_partial_auto=
                                          legacy_partial_auto)
            lowered = step.lower(*args)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower

            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
            from repro.launch import hlo_analysis as ha
            agg = ha.analyze(hlo)

            params_struct = args[0]
            n_params = sum(
                int(np_leaf.size) if hasattr(np_leaf, "size") else 0
                for np_leaf in jax.tree_util.tree_leaves(params_struct))
            mf = rl.model_flops(spec, SHAPES[shape_name], float(n_params))
            roof = rl.compute_roofline_from_aggregate(
                agg, chips, model_flops=mf)
            coll = rl.CollectiveStats(
                {k: int(v) for k, v in agg.collective_counts.items()},
                {k: int(v) for k, v in agg.collective_bytes.items()},
                int(agg.total_collective_bytes))

            mem_rec = {}
            if mem is not None:
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes",
                          "alias_size_in_bytes"):
                    v = getattr(mem, k, None)
                    if v is not None:
                        mem_rec[k] = int(v)
            rec.update(
                status="OK",
                lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
                n_params=n_params,
                cost={k: float(v) for k, v in (cost or {}).items()
                      if isinstance(v, (int, float))},
                memory=mem_rec,
                collectives=coll.to_dict(),
                roofline=roof.to_dict(),
            )
            if aux.get("aggregator") is not None:
                rec["schedule"] = _schedule_record(
                    aux["aggregator"], mesh, aux["dp_axes"],
                    aux["resolve_struct"], roof=roof,
                    collective_bytes=coll.bytes_by_kind,
                    model_axis_size=aux.get("model_axis_size"))
            if verbose:
                print(f"[dryrun] {arch} × {shape_name} × {rec['mesh']}: OK "
                      f"(lower {t_lower:.1f}s, compile {t_compile:.1f}s)")
                print(f"  memory_analysis: {mem_rec}")
                print(f"  cost_analysis: flops={rec['cost'].get('flops', 0):.3e}"
                      f" bytes={rec['cost'].get('bytes accessed', 0):.3e}")
                print(f"  collectives: {coll.counts} "
                      f"total={coll.total_bytes/2**20:.1f} MiB")
                print(f"  roofline: compute={roof.compute_s*1e3:.2f}ms "
                      f"memory={roof.memory_s*1e3:.2f}ms "
                      f"collective={roof.collective_s*1e3:.2f}ms "
                      f"dominant={roof.dominant}")
                sched = rec.get("schedule")
                if sched:
                    algs = sched["decomposition"]
                    print(f"  schedule: {sched['n_buckets']} buckets "
                          f"[{algs}] predicted="
                          f"{sched['predicted_comm_s']*1e3:.2f}ms "
                          f"charged={sched['charged_comm_s']*1e3:.2f}ms")
                    wc = sched.get("wire_check") or {}
                    if wc:
                        print(f"  wire: predicted "
                              f"{wc['predicted_total']/2**20:.1f} MiB vs "
                              f"charged {wc['charged_total']/2**20:.1f} "
                              f"MiB — "
                              + ("consistent" if wc["consistent"]
                                 else "MISMATCH"))
                    ov = sched["overlap"]
                    print(f"  overlap: {ov['overlap_fraction']*100:.0f}% "
                          f"of comm hidden — step "
                          f"{ov['step_serial_s']*1e3:.2f}ms serial -> "
                          f"{ov['step_overlapped_s']*1e3:.2f}ms "
                          f"overlapped (exposed "
                          f"{ov['exposed_comm_s']*1e3:.2f}ms)")
    except Exception as e:  # noqa: BLE001 — recorded, not swallowed
        rec.update(status="FAIL", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        if verbose:
            print(f"[dryrun] {arch} × {shape_name} × {rec['mesh']}: "
                  f"FAIL {e}")
    if trace_path and rec["status"] == "OK":
        try:
            _attach_trace(rec, arch, shape_name, mesh, strategy,
                          fusion_mb, sharding_aware, remat, wire_dtype,
                          spec_overrides, selector_mode, selector_table,
                          overlap, codec, error_feedback, trace_path,
                          verbose=verbose,
                          legacy_partial_auto=legacy_partial_auto)
        except Exception as te:  # noqa: BLE001 — recorded, not raised
            rec["measured"] = {"error": f"{type(te).__name__}: {te}"}
            if verbose:
                print(f"  trace: FAILED ({te})")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--strategy", default="rhd_rsa",
                    help="a reducers.STRATEGIES name, or 'auto' for "
                         "per-bucket message-size-aware selection")
    ap.add_argument("--selector-mode", default="analytic",
                    choices=["analytic", "empirical"])
    ap.add_argument("--selector-table", default="",
                    help="tuning-table JSON for --selector-mode empirical "
                         "(e.g. BENCH_allreduce.json)")
    ap.add_argument("--fusion-mb", type=float, default=4.0)
    ap.add_argument("--overlap", action="store_true",
                    help="issue per-bucket reductions inside the backward "
                         "(aggregator.overlap_params; DESIGN.md §3.6)")
    ap.add_argument("--no-sharding-aware", action="store_true")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--wire-dtype", default="")
    ap.add_argument("--codec", default="",
                    help="wire codec spec (core/codec.py): bf16 | int8 | "
                         "fp8_e4m3, or '<inner>x<outer>' per mesh level")
    ap.add_argument("--error-feedback", action="store_true",
                    help="carry the quantization residual into the next "
                         "step (requires --codec)")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--legacy-partial-auto", action="store_true",
                    help="lower the model axis AUTO under GSPMD "
                         "(partial-auto shard_map, the lowering "
                         "seq_parallel needs) instead of the default "
                         "full-manual region")
    ap.add_argument("--override", action="append", default=[],
                    help="spec override k=v (int/float/bool literal)")
    ap.add_argument("--json")
    ap.add_argument("--trace", default="",
                    help="write a Perfetto/Chrome trace_event JSON here "
                         "and attach the measured-replay residual table "
                         "(repro.telemetry.closure) to the record")
    args = ap.parse_args()

    from repro.configs import SHAPES, list_archs

    if args.all:
        records = []
        for arch in list_archs():
            for shape in SHAPES:
                records.append(run_one(arch, shape, args.multi_pod,
                                       args.strategy, args.fusion_mb,
                                       not args.no_sharding_aware))
        out = records
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required (or --all)")
        overrides = {"seq_parallel": True} if args.seq_parallel else {}
        for kv in args.override:
            k, v = kv.split("=", 1)
            try:
                overrides[k] = json.loads(v)
            except json.JSONDecodeError:
                overrides[k] = v
        overrides = overrides or None
        out = run_one(args.arch, args.shape, args.multi_pod, args.strategy,
                      args.fusion_mb, not args.no_sharding_aware,
                      remat=args.remat, wire_dtype=args.wire_dtype,
                      spec_overrides=overrides,
                      selector_mode=args.selector_mode,
                      selector_table=args.selector_table,
                      overlap=args.overlap, codec=args.codec,
                      error_feedback=args.error_feedback,
                      trace_path=args.trace,
                      legacy_partial_auto=args.legacy_partial_auto)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    ok = all(r["status"] != "FAIL" for r in
             (out if isinstance(out, list) else [out]))
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
