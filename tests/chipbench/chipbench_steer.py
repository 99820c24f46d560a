"""How the CPU tests steer a benchmark run: the device check passed, the
persistent cache off, every cell shrunk to a size the CPU runs in a few
seconds, and a CPU profile's op events standing in for a device plane."""
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from chipbench import harness, roofline  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402
from chipbench.trace import Event  # noqa: E402



def _with_pending(bench: dict) -> dict:
    """``BENCHMARK.json`` with the entries of the cells whose files are in
    ``chipbench/`` but that it does not hold yet (``pending_cells.json``),
    so that their code is rehearsed too."""
    pending = json.loads((Path(__file__).parent / "pending_cells.json")
                         .read_text())
    return dict(bench, **{k: bench[k] + pending[k] for k in
                          ("configs", "workloads", "end_to_end",
                           "per_layer")})


BENCH = _with_pending(json.loads((REPO / "BENCHMARK.json").read_text()))
CELLS = [w["name"] for w in BENCH["workloads"]]
SEED = 2**31 + 12345
_load = trace_mod.load


def shrink(cell):
    """The cell at a size the CPU runs in a few seconds."""
    cell.config = dict(cell.config, grid_side=12, block_size=64)
    if "capacity" in cell.config:
        cell.config.update(capacity=4, offered_per_s=40.0)
        cell.params = dict(cell.params, warm_requests=4)
    return cell


def cpu_as_device(path):
    """A CPU profile has no device plane: its XLA op events stand in for
    one, and each batch span for a megastep execution, so that the traced
    path runs to its end."""
    t = _load(path)
    spans = {"chipbench.batch", "chipbench.drive", trace_mod.WINDOW_SPAN}
    t.ops = [[e for e in t.host if "::" not in e.name and e.name not in spans
              and not e.name.startswith("$")]]
    t.modules = [[Event("jit_megastep(1)", e.start_ns, e.end_ns)
                  for e in t.host if e.name == "chipbench.batch"]]
    return t


def with_pending(monkeypatch):
    monkeypatch.setattr(harness, "benchmark", lambda: BENCH)


def steer(monkeypatch, tmp_path):
    import jax
    with_pending(monkeypatch)
    monkeypatch.setattr(harness, "devices", lambda chips: jax.devices()[:1])
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    monkeypatch.setattr(trace_mod, "load", cpu_as_device)
    real = harness.load_cell
    monkeypatch.setattr(harness, "load_cell", lambda n: shrink(real(n)))
    # the CPU is in no peaks table: stand the v5e's row in for it
    monkeypatch.setattr(roofline, "peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})


def run_cell(name, traced, capsys, seconds=0.5):
    """One steered run; (result line, standard error)."""
    rc = harness.main(["--workload", name, "--seed", str(SEED),
                       "--seconds", str(seconds), "--trace",
                       str(int(traced))])
    out, err = capsys.readouterr()
    assert rc == 0, err
    return json.loads(out.strip().splitlines()[-1]), err
