"""Compiled-artifact contract auditor (rules RL007-RL009).

The source-level rules (RL001-RL006) prove properties of the *Python*
tree; every contract that actually determines the engine's claimed
efficiency lives in the *compiled* artifact: one fused chunk program
with no hidden host round-trips, a scenario batch axis that shards
without resharding, donated carries that are really input-output
aliased, a fold buffer whose dtype follows the x64 mode, a bounded
device-memory footprint. This module AOT-lowers every registered
compile site (the RL002 registry) on representative hull shapes —
through the SAME seams the engine executes (``_prepare_sweep_args`` +
``_sweep_runner``, ``_sim_program``, ``_reactive_program``) — and
checks the compiled artifact against the committed contracts in
``artifact_contracts.toml``:

* **RL008** — collective inventory (analysis/hlo.py ``parse_collectives``)
  must stay inside the unit's allow-list (empty for the chunk program:
  scenarios are independent lanes, sharding them must not introduce
  all-gather/all-reduce/reshard traffic), and the program must contain
  no host callbacks / infeed / outfeed / send / recv.
* **RL009** — donation verification: off-CPU the runner's donated
  carries must be aliased (``memory_analysis().alias_size_in_bytes``);
  on CPU — where the runner deliberately omits ``donate_argnames`` — a
  forced-donation probe compiles the same program WITH donation and
  requires full aliasing, so a carry-structure drift that would break
  donation on an accelerator is caught on the CPU CI.
* **RL007** — contract drift: fold-buffer dtype under the current x64
  mode, peak-device-memory budget, measured ``cost_analysis()``
  FLOPs/bytes vs the blessed per-mode bands, registry coverage (every
  RL002 compile site maps to an audit unit or an ``[[artifact.skip]]``
  with a reason), and the planner cost-model calibration (the
  model-vs-measured ratio must stay within ``max_ratio_spread`` across
  hulls — see ``calibration`` in the payload and
  ``planner.plan_sites(cost_model="hlo")``).

Bless workflow: ``python -m repro.analysis --bless-artifacts`` measures
the current tree and rewrites the contract file's per-mode measured
tables (budgets are only filled when missing, never tightened
silently); RL008/RL009 violations are never blessable. The text-level
checkers are pure functions over HLO text / stat dicts so the fixture
corpus (tests/test_artifact.py) can pin rule IDs without compiling.
"""
from __future__ import annotations

from pathlib import Path

from . import hlo, toml_lite
from .findings import Finding

ARTIFACT_RELPATH = "src/repro/analysis/artifact_contracts.toml"

#: bump when the contract schema or the audit semantics change
ARTIFACT_SCHEMA_VERSION = 1


def load_contracts(root: Path, path: Path | None = None) -> dict:
    p = path or (Path(root) / ARTIFACT_RELPATH)
    return toml_lite.load(p)


def _mode_key() -> str:
    import jax
    return "x64" if jax.config.jax_enable_x64 else "x32"


# ---------------------------------------------------------------------------
# text/stat-level checkers (pure: the fixture corpus drives these)
# ---------------------------------------------------------------------------

def check_collectives_text(hlo_text: str, allowed, path: str,
                           where: str) -> list:
    """RL008: collective ops outside the allow-list."""
    out = []
    allowed = set(allowed or [])
    for op, d in hlo.parse_collectives(hlo_text).by_op().items():
        if op not in allowed:
            out.append(Finding(
                "RL008", path, 1,
                f"{where}: compiled program contains {d['count']}x "
                f"{op} ({d['link_bytes']:.0f} link-bytes) — the "
                "scenario batch axis must not communicate (independent "
                "lanes); extend the unit's collectives_allowed only "
                "with a reviewed contract edit"))
    return out


def check_host_ops_text(hlo_text: str, path: str, where: str) -> list:
    """RL008: host callbacks / infeed / outfeed / send / recv."""
    ops = hlo.find_host_ops(hlo_text)
    if not ops:
        return []
    uniq = sorted(set(ops))
    return [Finding(
        "RL008", path, 1,
        f"{where}: compiled program contains host-communication op(s) "
        f"{uniq} ({len(ops)} total) — the chunk program must stay "
        "device-resident (no io_callback/debug.print/infeed/outfeed)")]


def check_donation(mem: dict, alias_entries: int, donated_bytes: int,
                   min_alias_frac: float, path: str, where: str) -> list:
    """RL009: donated carries must be input-output aliased."""
    alias = int(mem.get("alias_size_in_bytes", 0))
    if donated_bytes <= 0:
        return []
    if alias >= min_alias_frac * donated_bytes and alias_entries > 0:
        return []
    return [Finding(
        "RL009", path, 1,
        f"{where}: donation lost — {alias}/{donated_bytes} donated "
        f"carry bytes aliased ({alias_entries} alias entries, need "
        f">= {min_alias_frac:.0%}); a carry input/output structure or "
        "dtype mismatch is blocking XLA buffer donation")]


def check_fold_dtype(found: str, expected: str, path: str,
                     where: str) -> list:
    """RL007: the fold-buffer dtype must follow the x64 mode."""
    if found == expected:
        return []
    return [Finding(
        "RL007", path, 1,
        f"{where}: fold buffer dtype is {found}, contract expects "
        f"{expected} for this x64 mode — the Kahan fold precision "
        "contract (_fold_dtype) drifted")]


def check_memory_budget(mem: dict, budget: int, path: str,
                        where: str) -> list:
    """RL007: peak device memory (temp + output) within budget."""
    peak = int(mem.get("temp_size_in_bytes", 0)) \
        + int(mem.get("output_size_in_bytes", 0))
    if budget and peak > budget:
        return [Finding(
            "RL007", path, 1,
            f"{where}: peak device memory {peak} B exceeds the "
            f"contract budget {budget} B (temp "
            f"{mem.get('temp_size_in_bytes', 0)} + output "
            f"{mem.get('output_size_in_bytes', 0)}); re-bless only "
            "after reviewing what grew")]
    return []


def check_cost_drift(measured: dict, blessed: dict | None, rtol: float,
                     mode: str, path: str, where: str) -> list:
    """RL007: measured cost_analysis() vs the blessed per-mode band."""
    if blessed is None:
        return [Finding(
            "RL007", path, 1,
            f"{where}: no blessed {mode} measurement in "
            "artifact_contracts.toml — run `python -m repro.analysis "
            "--bless-artifacts` under this mode and commit the "
            "contract update")]
    out = []
    for key, label in (("flops_per_scen", "FLOPs"),
                       ("bytes_per_scen", "bytes-accessed")):
        m, b = measured.get(key), blessed.get(key)
        if not b:
            continue
        if abs(m - b) > rtol * b:
            out.append(Finding(
                "RL007", path, 1,
                f"{where}: measured {label} {m:.0f} drifted beyond "
                f"±{rtol:.0%} of the blessed {b:.0f} ({mode}) — the "
                "compiled cost moved; review and re-bless"))
    return out


def check_coverage(cfg, art: dict) -> list:
    """RL007: every RL002 compile site maps to an audit unit's covers
    list or an [[artifact.skip]] entry with a reason."""
    out = []
    covers = []
    for u in art.get("unit", []):
        covers.extend(u.get("covers", []))
    skips = {}
    for s in art.get("skip", []):
        key = f"{s.get('file', '')}::{s.get('qualname', '')}"
        skips[key] = s
        if not str(s.get("reason", "")).strip():
            out.append(Finding(
                "RL007", ARTIFACT_RELPATH, 1,
                f"artifact.skip entry {key} carries no reason"))
    for e in cfg.raw.get("compile_site", []):
        key = f"{e.get('file', '')}::{e.get('qualname', '')}"
        covered = key in skips or any(
            key == c or key.startswith(c + ".") for c in covers)
        if not covered:
            out.append(Finding(
                "RL007", ARTIFACT_RELPATH, 1,
                f"registry compile site {key} is not covered by any "
                "artifact audit unit — add it to a unit's covers list "
                "or declare an [[artifact.skip]] with a reason"))
    return out


def check_calibration(cal: dict, max_spread: float) -> list:
    """RL007: the hand cost model must track measured HLO cost — the
    per-hull model-vs-measured ratio spread stays bounded."""
    spread = cal.get("ratio_spread", 1.0)
    if spread <= max_spread:
        return []
    hulls = ", ".join(f"{h['tag']}:{h['ratio']:.1f}"
                      for h in cal.get("hulls", []))
    return [Finding(
        "RL007", "src/repro/core/planner.py", 1,
        f"planner cost-model calibration: model-vs-measured ratio "
        f"spread {spread:.2f} exceeds max_ratio_spread {max_spread} "
        f"across hulls ({hulls}) — site_cost mis-scales with hull "
        "size and would mis-bucket sweeps; recalibrate the footprint "
        "model or switch the sweep to cost_model='hlo'")]


# ---------------------------------------------------------------------------
# unit builders (lazy jax: lint-only runs never import it)
# ---------------------------------------------------------------------------

def _case_site(case: dict):
    from repro.core.topology import FBSite
    return FBSite(n_clusters=int(case["ncl"]),
                  racks_per_cluster=int(case["rpc"]),
                  servers_per_rack=int(case["spr"]),
                  csw_per_cluster=int(case["cpc"]),
                  n_fc=int(case["nfc"]))


def _tree_nbytes(tree) -> int:
    import jax
    import numpy as np
    return int(sum(np.prod(a.shape) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree)))


def _audit_sweep_case(unit: dict, case: dict, art: dict, mode: str,
                      bless: bool):
    import jax
    import jax.numpy as jnp

    from repro.core import simulator as S
    from repro.core.traffic import TRAFFIC_SPECS

    site = _case_site(case)
    n = int(case.get("n_scenarios", 2))
    length = int(case.get("length", 16))
    validate = bool(case.get("validate", False))
    spec = TRAFFIC_SPECS[case.get("spec", "fb_hadoop")]
    runs = [(S.SimParams(spec=spec, site=site), i) for i in range(n)]
    batch = S.make_batch(runs)
    hull = batch.hull
    scen, state, fold, guard, tol = S._prepare_sweep_args(
        batch, fold="device", validate=validate)
    live = jnp.ones((length,), bool)
    ci = jnp.asarray(0, jnp.int32) if validate else None

    runner = S._runner_for(scen)
    comp = runner.lower(hull, scen, state, length, live, fold, guard,
                        ci, tol, validate).compile()
    txt = comp.as_text()
    mem = hlo.memory_stats(comp)
    cost = hlo.cost_stats(comp)

    # per-scenario normalization keeps the measurement leg-invariant:
    # under scenario-axis sharding cost_analysis() reports one shard
    n_padded = int(jax.tree.leaves(scen)[0].shape[0])
    n_shards = jax.local_device_count() \
        if S._should_shard(len(batch), None) else 1
    per_scen = max(n_padded // n_shards, 1)
    measured = {
        "flops_per_scen": cost["flops"] / per_scen,
        "bytes_per_scen": cost["bytes_accessed"] / per_scen,
    }

    path, where = unit.get("file", ARTIFACT_RELPATH), \
        f"sweep_chunk[{case.get('tag', '?')}]"
    findings = []
    findings += check_collectives_text(
        txt, unit.get("collectives_allowed", []), path, where)
    findings += check_host_ops_text(txt, path, where)
    findings += check_memory_budget(
        mem, int(case.get("peak_bytes_budget", 0)), ARTIFACT_RELPATH,
        where)

    # fold dtype under the current x64 mode (base, non-validate cases)
    fold_dtype = None
    if not validate:
        out_shape = jax.eval_shape(
            lambda sc, st, fo: S._sweep_chunk_impl(
                hull, sc, st, length, live, fo, None, None, None,
                False),
            scen, state, fold)
        dts = {str(a.dtype) for a in jax.tree.leaves(out_shape[1])}
        fold_dtype = sorted(dts)[0] if len(dts) == 1 else str(sorted(dts))
        expected = unit.get(f"fold_dtype_{mode}",
                            "float64" if mode == "x64" else "float32")
        findings += check_fold_dtype(fold_dtype, expected, path, where)

    # donation: off-CPU the real runner must alias; on CPU force it
    # through a probe so carry-structure drift is caught before TPU
    donation = unit.get("donation", "none")
    alias_info = None
    if donation == "off-cpu" and bool(case.get("donation_probe", False)):
        donated = _tree_nbytes(state) + _tree_nbytes(fold)
        min_frac = float(art.get("min_alias_frac", 1.0))
        if jax.default_backend() == "cpu":
            # structural probe, unsharded leg only: under scenario-axis
            # sharding memory_analysis() reports per-shard alias sizes
            # against whole-array donated bytes, so the 100% fraction
            # cannot be stated; the 1-device canary leg pins it
            if n_shards == 1:
                probe = jax.jit(S._sweep_chunk_impl,
                                static_argnames=("site", "length",
                                                 "validate"),
                                donate_argnames=("state", "fold"))
                pcomp = probe.lower(hull, scen, state, length, live,
                                    fold, guard, ci, tol,
                                    validate).compile()
                pmem = hlo.memory_stats(pcomp)
                entries = hlo.count_alias_entries(pcomp.as_text())
                findings += check_donation(
                    pmem, entries, donated, min_frac, path,
                    where + "+donation-probe")
                alias_info = {"probe": True,
                              "alias_size": pmem["alias_size_in_bytes"],
                              "entries": entries,
                              "donated_bytes": donated}
        else:
            entries = hlo.count_alias_entries(txt)
            findings += check_donation(mem, entries, donated, min_frac,
                                       path, where)
            alias_info = {"probe": False,
                          "alias_size": mem["alias_size_in_bytes"],
                          "entries": entries, "donated_bytes": donated}

    if not bless:
        blessed = case.get("measured", {}).get(mode)
        findings += check_cost_drift(
            measured, blessed, float(art.get("cost_rtol", 0.5)), mode,
            ARTIFACT_RELPATH, where)

    payload = {"tag": case.get("tag"), "validate": validate,
               "measured": measured, "memory": mem,
               "collectives": hlo.parse_collectives(txt).by_op(),
               "host_ops": len(hlo.find_host_ops(txt)),
               "fold_dtype": fold_dtype, "alias": alias_info,
               "n_scenarios": n, "length": length,
               "shards": n_shards}
    if bless:
        case.setdefault("measured", {})[mode] = {
            k: round(v, 1) for k, v in measured.items()}
        if not case.get("peak_bytes_budget"):
            peak = mem["temp_size_in_bytes"] + mem["output_size_in_bytes"]
            case["peak_bytes_budget"] = 4 * peak
    return findings, payload


def _audit_run_sim_case(unit: dict, case: dict, art: dict, mode: str,
                        bless: bool):
    import jax

    from repro.core import simulator as S
    from repro.core.traffic import TRAFFIC_SPECS

    site = _case_site(case)
    n_ticks = int(case.get("n_ticks", 64))
    spec = TRAFFIC_SPECS[case.get("spec", "fb_hadoop")]
    params = S.SimParams(spec=spec, site=site)
    batch = S.make_batch([(params, 0)])
    hull = batch.hull
    scen = jax.tree.map(lambda x: x[0], batch.scen)
    state = S._init_state(hull, scen, jax.random.PRNGKey(0))
    go = S._sim_program(hull, scen, n_ticks)
    comp = go.lower(state).compile()
    txt = comp.as_text()
    mem = hlo.memory_stats(comp)
    cost = hlo.cost_stats(comp)
    measured = {"flops_per_scen": cost["flops"],
                "bytes_per_scen": cost["bytes_accessed"]}

    path = unit.get("file", ARTIFACT_RELPATH)
    where = f"run_sim[{case.get('tag', '?')}]"
    findings = []
    findings += check_collectives_text(
        txt, unit.get("collectives_allowed", []), path, where)
    findings += check_host_ops_text(txt, path, where)
    findings += check_memory_budget(
        mem, int(case.get("peak_bytes_budget", 0)), ARTIFACT_RELPATH,
        where)
    if not bless:
        findings += check_cost_drift(
            measured, case.get("measured", {}).get(mode),
            float(art.get("cost_rtol", 0.5)), mode, ARTIFACT_RELPATH,
            where)
    payload = {"tag": case.get("tag"), "measured": measured,
               "memory": mem,
               "collectives": hlo.parse_collectives(txt).by_op(),
               "host_ops": len(hlo.find_host_ops(txt))}
    if bless:
        case.setdefault("measured", {})[mode] = {
            k: round(v, 1) for k, v in measured.items()}
        if not case.get("peak_bytes_budget"):
            peak = mem["temp_size_in_bytes"] + mem["output_size_in_bytes"]
            case["peak_bytes_budget"] = 4 * peak
    return findings, payload


def _audit_ici_case(unit: dict, case: dict, art: dict, mode: str,
                    bless: bool):
    import numpy as np

    from repro.core import constants as C
    from repro.core import ici_gating

    n_ticks = int(case.get("n_ticks", 256))
    tick_us = float(case.get("tick_us", 1.0))
    links = C.TPU_ICI_LINKS_PER_CHIP
    bw_link_tick = C.TPU_ICI_LINK_BW * 1e-6 * tick_us
    cap_q = 8 * bw_link_tick
    up_delay = max(int(np.ceil(C.LASER_ON_US / tick_us)), 1)
    run = ici_gating._reactive_program(links, bw_link_tick, tick_us,
                                       cap_q, up_delay)
    comp = run.lower(np.zeros(n_ticks)).compile()
    txt = comp.as_text()
    mem = hlo.memory_stats(comp)
    cost = hlo.cost_stats(comp)
    measured = {"flops_per_scen": cost["flops"],
                "bytes_per_scen": cost["bytes_accessed"]}

    path = unit.get("file", ARTIFACT_RELPATH)
    where = f"ici_reactive[{case.get('tag', '?')}]"
    findings = []
    findings += check_collectives_text(
        txt, unit.get("collectives_allowed", []), path, where)
    findings += check_host_ops_text(txt, path, where)
    findings += check_memory_budget(
        mem, int(case.get("peak_bytes_budget", 0)), ARTIFACT_RELPATH,
        where)
    if not bless:
        findings += check_cost_drift(
            measured, case.get("measured", {}).get(mode),
            float(art.get("cost_rtol", 0.5)), mode, ARTIFACT_RELPATH,
            where)
    payload = {"tag": case.get("tag"), "measured": measured,
               "memory": mem,
               "collectives": hlo.parse_collectives(txt).by_op(),
               "host_ops": len(hlo.find_host_ops(txt))}
    if bless:
        case.setdefault("measured", {})[mode] = {
            k: round(v, 1) for k, v in measured.items()}
        if not case.get("peak_bytes_budget"):
            peak = mem["temp_size_in_bytes"] + mem["output_size_in_bytes"]
            case["peak_bytes_budget"] = 4 * peak
    return findings, payload


_BUILDERS = {
    "sweep_chunk": _audit_sweep_case,
    "run_sim": _audit_run_sim_case,
    "ici_reactive": _audit_ici_case,
}


# ---------------------------------------------------------------------------
# planner cost-model calibration
# ---------------------------------------------------------------------------

def calibration(art: dict, unit_payloads: dict) -> dict:
    """Model-vs-measured cost per audited hull: ratio = measured HLO
    FLOPs per (scenario, tick) over ``planner.site_cost`` units. Only
    RATIOS matter for bucketing, so the hand model is healthy iff the
    ratio is stable across hulls (``ratio_spread`` = max/min).

    Each hull also reports its arithmetic intensity (HLO FLOPs /
    bytes-accessed) against the TPU ridge point (peak FLOPs / HBM BW,
    the benchmarks/roofline.py constants): ``site_cost`` models the
    step as bandwidth-bound elementwise work, and ``ridge_frac`` << 1
    is that premise made measurable."""
    from repro.core import constants as C
    from repro.core import planner
    from repro.core.topology import site_tag

    ridge = C.TPU_PEAK_BF16_FLOPS / C.TPU_HBM_BW
    hulls = []
    for u in art.get("unit", []):
        if u.get("builder") != "sweep_chunk":
            continue
        pays = unit_payloads.get(u.get("name"), {}).get("cases", [])
        by_tag = {p.get("tag"): p for p in pays}
        for case in u.get("case", []):
            if case.get("validate"):
                continue                   # guard math skews the ratio
            p = by_tag.get(case.get("tag"))
            if not p:
                continue
            site = _case_site(case)
            model = planner.site_cost(site)
            meas = p["measured"]["flops_per_scen"] / max(
                int(case.get("length", 16)), 1)
            intensity = p["measured"]["flops_per_scen"] / max(
                p["measured"]["bytes_per_scen"], 1e-12)
            hulls.append({"tag": site_tag(site), "model_cost": model,
                          "measured_flops_per_tick_scen": meas,
                          "ratio": meas / max(model, 1e-12),
                          "arith_intensity": intensity,
                          "ridge_frac": intensity / ridge})
    ratios = [h["ratio"] for h in hulls]
    spread = (max(ratios) / max(min(ratios), 1e-12)) if ratios else 1.0
    import math
    k = math.exp(sum(math.log(max(r, 1e-12)) for r in ratios)
                 / len(ratios)) if ratios else 1.0
    return {"hulls": hulls, "ratio_spread": spread,
            "mean_ratio": k}


# ---------------------------------------------------------------------------
# contract file emitter (the --bless-artifacts writer)
# ---------------------------------------------------------------------------

_HEADER = """\
# Compiled-artifact contracts for repro.analysis.artifact (RL007-RL009;
# see ROADMAP "Static contracts"). Measured tables are per x64 mode and
# written by `python -m repro.analysis --bless-artifacts` — regenerable
# audit JSON lives under results/ (gitignored), ONLY this blessed file
# is committed. Budgets and allow-lists are reviewed edits: blessing
# never tightens a budget and never blesses a collective/callback in.
"""


def _fmt(v) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, str):
        return '"' + v.replace("\\", "\\\\").replace('"', '\\"') + '"'
    if isinstance(v, float):
        return repr(v)
    if isinstance(v, int):
        return str(v)
    if isinstance(v, list):
        return "[" + ", ".join(_fmt(x) for x in v) + "]"
    raise TypeError(f"cannot emit {type(v).__name__} in contracts file")


def _emit_pairs(d: dict, lines: list):
    for k, v in d.items():
        if isinstance(v, dict):
            continue                      # sub-tables emit their own header
        if isinstance(v, list) and v and isinstance(v[0], dict):
            continue                      # arrays-of-tables likewise
        lines.append(f"{k} = {_fmt(v)}")


def dump_contracts(contracts: dict) -> str:
    art = contracts.get("artifact", {})
    lines = [_HEADER, "[artifact]"]
    _emit_pairs(art, lines)
    for s in art.get("skip", []):
        lines += ["", "[[artifact.skip]]"]
        _emit_pairs(s, lines)
    for u in art.get("unit", []):
        lines += ["", "[[artifact.unit]]"]
        _emit_pairs(u, lines)
        for c in u.get("case", []):
            lines += ["", "[[artifact.unit.case]]"]
            _emit_pairs(c, lines)
            meas = c.get("measured", {})
            for mode in sorted(meas):
                lines += ["", f"[artifact.unit.case.measured.{mode}]"]
                _emit_pairs(meas[mode], lines)
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# the audit driver
# ---------------------------------------------------------------------------

def run_audit(root: Path, cfg, contracts_path: Path | None = None, *,
              bless: bool = False, units=None):
    """Audit every contract unit; returns ``(findings, payload)``.

    With ``bless=True`` the per-mode measured tables (and missing
    budgets) are rewritten in place; drift checks are skipped (a fresh
    bless is definitionally in-band) but RL008/RL009 violations still
    fire — collectives, callbacks and donation loss are never
    blessable. ``units`` restricts the audit to the named units (the
    coverage check is skipped for partial audits).
    """
    import jax

    root = Path(root)
    cpath = Path(contracts_path) if contracts_path \
        else root / ARTIFACT_RELPATH
    contracts = toml_lite.load(cpath)
    art = contracts.get("artifact", {})
    mode = _mode_key()

    findings = []
    if int(art.get("schema_version", 0)) != ARTIFACT_SCHEMA_VERSION:
        findings.append(Finding(
            "RL007", ARTIFACT_RELPATH, 1,
            f"artifact contract schema_version "
            f"{art.get('schema_version')} != auditor "
            f"{ARTIFACT_SCHEMA_VERSION} (bump both together)"))
    if units is None:
        findings += check_coverage(cfg, art)

    unit_payloads = {}
    for u in art.get("unit", []):
        name = u.get("name", "?")
        if units is not None and name not in units:
            continue
        builder = _BUILDERS.get(u.get("builder", ""))
        if builder is None:
            findings.append(Finding(
                "RL007", ARTIFACT_RELPATH, 1,
                f"artifact unit {name!r} names unknown builder "
                f"{u.get('builder')!r} (known: "
                f"{sorted(_BUILDERS)})"))
            continue
        cases = []
        for case in u.get("case", []):
            f, p = builder(u, case, art, mode, bless)
            findings += f
            cases.append(p)
        unit_payloads[name] = {"builder": u.get("builder"),
                               "cases": cases}

    cal = calibration(art, unit_payloads)
    if cal["hulls"]:
        findings += check_calibration(
            cal, float(art.get("max_ratio_spread", 2.0)))

    if bless:
        cpath.write_text(dump_contracts(contracts))

    payload = {
        "schema_version": ARTIFACT_SCHEMA_VERSION,
        "mode": {"x64": mode == "x64",
                 "backend": jax.default_backend(),
                 "devices": jax.local_device_count(),
                 "jax": jax.__version__},
        "blessed": bless,
        "units": unit_payloads,
        "calibration": cal,
    }
    return findings, payload


def hlo_cost_table(root: Path | None = None,
                   contracts_path: Path | None = None,
                   mode: str = "x32") -> dict:
    """Blessed per-hull cost table for ``planner.plan_sites(
    cost_model="hlo")``: ``full_site_tag -> {"flops_per_tick_scen",
    "site"}``. Reads only the committed contract file (no jax), so the
    planner stays importable without an accelerator stack."""
    from repro.core.topology import full_site_tag

    if contracts_path is None:
        base = Path(root) if root is not None \
            else Path(__file__).resolve().parents[3]
        contracts_path = base / ARTIFACT_RELPATH
    art = toml_lite.load(contracts_path).get("artifact", {})
    table = {}
    for u in art.get("unit", []):
        if u.get("builder") != "sweep_chunk":
            continue
        for case in u.get("case", []):
            if case.get("validate"):
                continue
            blessed = case.get("measured", {}).get(mode)
            if not blessed:
                continue
            site = _case_site(case)
            length = max(int(case.get("length", 16)), 1)
            table[full_site_tag(site)] = {
                "flops_per_tick_scen":
                    float(blessed["flops_per_scen"]) / length,
                "site": site,
            }
    return table
