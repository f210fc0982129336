"""Boot-recorder chaos acceptance (ISSUE 16): mid-soak a COLD third
replica is built from nothing under its own boot recorder, warms, and
joins the live pool — and the soak report proves:

1. **Fully-populated TTFST decomposition.** The artifact's ``boot``
   block carries every scoped stage (weights_load with bytes/s,
   engine_init, warmup_compile with its manifest size,
   warm_prefix_copies) and every milestone (listener_up, first_probe,
   first_served_token) at monotonic offsets, and the stage seconds sum
   to no more than the sealed TTFST — the decomposition is internally
   consistent, not a grab-bag of timers.
2. **Zero client 5xx.** Joining a cold replica next to live traffic
   never surfaces an error to a client: requests route to it only
   after the probe loop promotes it.
3. **Goodput holds through the join.** The scored ``scale_up`` window
   still serves, and the overall soak goodput stays at baseline
   levels — adding capacity is never worse than not adding it.

Seconds-scale but deliberately longer than the kill/drain soak: the
cold replica's mid-soak warmup walks the full shape-bucket grid while
competing with live traffic for the same cores, so the schedule must
outlive boot + join + enough post-join traffic to seal TTFST (warmup
kernels come from the shared test compile cache; loading them is the
dominant boot cost on CPU).
"""

from dstack_tpu.loadgen import compile_schedule, default_spec
from dstack_tpu.loadgen.soak import SoakConfig, run_soak

SEED = 11
DURATION = 30.0
RATE = 3.0


class TestBootChaosAcceptance:
    def test_cold_replica_scale_up_under_open_loop_load(self):
        schedule = compile_schedule(
            default_spec(duration_s=DURATION, rate_rps=RATE), SEED
        )
        assert len(schedule.events) >= 10, "workload too thin to prove anything"
        cfg = SoakConfig(
            replicas=2,
            chaos=False,  # isolate the scale-up: no drain, no kill
            scale_up=True,
            scale_up_frac=0.1,  # spawn early: the boot must finish
            scale_up_window_s=10.0,
            output=None,
        )
        report = run_soak(schedule, cfg)

        # the soak replayed the seeded workload, all of it
        assert report["schedule_digest"] == schedule.digest()
        assert report["overall"]["requests"] == len(schedule.events)

        # (2) zero client 5xx while a cold replica boots and joins
        assert report["client_5xx"] == 0, report["overall"]["outcomes"]
        assert report["failures"] == 0, report["overall"]["outcomes"]

        # (1) the TTFST decomposition is fully populated
        boot = report["boot"]
        assert boot is not None, "scale_up soak must emit a boot block"
        assert boot["replica"] == "r2"
        assert boot["boot_id"]
        assert boot["t_spawn"] > 0.0
        stages = boot["stages"]
        for name in (
            "weights_load", "engine_init", "warmup_compile",
            "warm_prefix_copies",
        ):
            assert stages.get(name, 0.0) > 0.0, (name, stages)
        marks = boot["marks"]
        for name in ("listener_up", "first_probe", "first_served_token"):
            assert marks.get(name) is not None, (name, marks)
        # milestones in causal order: the listener is up before the
        # probe loop can see the replica, and it serves only after
        assert marks["listener_up"] <= marks["first_probe"]
        assert marks["first_probe"] <= marks["first_served_token"]
        assert boot["time_to_ready_s"] == marks["first_probe"]
        assert boot["ttfst_s"] == marks["first_served_token"]
        # internal consistency: the sequential scoped stages cannot sum
        # past the sealed TTFST they decompose
        assert sum(stages.values()) <= boot["ttfst_s"] + 1e-6, boot
        assert boot["warm"] is True  # it finished warmup and served
        # the warmup visited real compile variants (the manifest the
        # steady-state gap detector checks against)
        assert boot["manifest_variants"] >= 1
        # the timeline carries the same story entry-by-entry, with the
        # weights stage's honest bytes + derived throughput
        tl = boot["timeline"]
        by_stage = {e["stage"]: e for e in tl}
        assert by_stage["weights_load"]["bytes"] > 0
        assert by_stage["weights_load"]["bytes_per_s"] > 0
        assert by_stage["warmup_compile"]["manifest"] >= 1
        ts = [e["t"] for e in tl]
        assert ts == sorted(ts), "timeline offsets must be monotonic"

        # (3) the join window served and overall goodput held
        up = report["windows"]["scale_up"]
        assert up["requests"] >= 1, up
        assert up["goodput_ratio"] is not None, up
        assert report["overall"]["goodput_ratio"] >= 0.5, (
            report["overall"]
        )

        # the artifact root names the device the stage durations were
        # taken on (CPU here: not chip boot numbers), and each replica
        # holds its cache on its own device
        assert report["device"]["platform"] == "cpu"
        placed = list(report["replica_devices"].values())
        assert len({tuple(d) for d in placed}) == len(placed), placed
