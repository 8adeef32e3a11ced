"""The rules that ``BENCHMARK.json`` and the files under ``benchmark/``
are held to, each a function of the root of a checkout: the tests call
them on this repository, and on a copy to which a configuration or a
cell was added as files. A rule that does not hold raises ``Refused``
and says what it found.

They say the same thing in both places, in the characters and lengths
the contract allows; every per-layer metric moves an end-to-end metric
that its cells report; which metrics a cell reports is said once; and a
configuration is held to its source by what it may cut (``cut``): depth,
experts held, vocabulary, a side module left out, and never a width.
"""

import json
import re
from pathlib import Path

from .run import merge, reported_by

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FILE = re.compile(r"^[A-Za-z0-9_.\-/]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {"configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source",
                       "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}}
# ``fields`` key -> key of ``published``, for a configuration's file that
# gives no ``published_as``: the OPT block's. A file's own map has these
# six at least: every block has them
OPT_PUBLISHED_AS = {"hidden_size": "hidden_size",
                    "intermediate_size": "ffn_dim",
                    "num_heads": "num_attention_heads",
                    "num_layers": "num_hidden_layers",
                    "vocab_size": "vocab_size",
                    "max_seq_len": "max_position_embeddings"}
# a key that names a width, in the source's spelling or the program's: a
# hidden, intermediate, latent, state or projection size, a head size, a
# window, an expansion factor, the experts per token
WIDTH = re.compile(
    r"(hidden|intermediate|latent|state|proj[a-z]*|head|embed[a-z]*)"
    r"_(size|dim|width)|_dim$|_rank$|(^|_)d_[a-z]+$|window|expan|per_tok"
    r"|top_k")
KINDS = ("depth", "experts", "vocabulary", "module")


class Refused(ValueError):
    """A rule of the manifest does not hold."""


def need(ok, why):
    if not ok:
        raise Refused(why)


def read(root):
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def load(root, kind, name):
    return json.loads(
        (Path(root) / "benchmark" / kind / f"{name}.json").read_text())


def names(root, kind):
    return sorted(p.name[:-len(".json")] for p in
                  (Path(root) / "benchmark" / kind).glob("*.json"))


def cells(bench):
    return [w["name"] for w in bench["workloads"]]


def reported_in(bench, metric):
    return metric.get("workloads", cells(bench))


def entries(bench):
    """Every entry of the four lists with the list it is in."""
    return [(kind, e) for kind in KEYS for e in bench[kind]]


def top_level(root):
    bench = read(root)
    need(set(bench) == {"command", "paths", "run_seconds", *KEYS},
         f"BENCHMARK.json has the keys {sorted(bench)}")
    need(bench["paths"] == ["benchmark", "tests/benchmark"], bench["paths"])
    need(bench["command"] == ["python3", "benchmark/run.py"],
         bench["command"])
    need(isinstance(bench["run_seconds"], int)
         and 1 <= bench["run_seconds"] <= 51, bench["run_seconds"])
    need(len((Path(root) / "BENCHMARK.json").read_bytes()) <= 64 * 1024,
         "BENCHMARK.json is over 64 KiB")
    need(1 <= len(bench["workloads"]) <= 24, "1 to 24 cells")
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    need(len(four) <= max(1, len(bench["workloads"]) // 4),
         f"{four} ask for four chips: over a quarter of the cells")
    setup = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    need(len(setup) == 1 and setup[0]["bound"] <= 0.1, "one setup_s")
    need(all(0.01 <= m["bound"] <= 0.1 for m in bench["end_to_end"]),
         "a bound lies between 0.01 and 0.1")


def entry(kind, e):
    """One entry's names, units, lines and keys."""
    need(NAME.match(e["name"]), e["name"])
    for key in ("config", "traffic", "moves"):
        need(key not in e or NAME.match(e[key]), (e["name"], key))
    if "unit" in e:
        need(UNIT.match(e["unit"]), (e["name"], e["unit"]))
        need(e["better"] in ("lower", "higher"), e["name"])
        need(e["source"] in SOURCES, (e["name"], e["source"]))
    for key in ("why", "layer", "source"):
        v = e.get(key, "-")
        need(1 <= len(v) <= 200 and "\n" not in v and "\t" not in v,
             f"{e['name']}: {key} is 1 to 200 characters on one line")
    need(set(e) <= KEYS[kind],
         f"{e['name']}: keys {sorted(set(e) - KEYS[kind])} are not allowed")
    for key in e.get("reduced", []):
        need(NAME.match(key), (e["name"], key))
    need(len(e.get("reduced", [])) <= 16, e["name"])


def every_entry(root):
    for kind, e in entries(read(root)):
        entry(kind, e)


def unique_names(root):
    bench = read(root)
    for group in (bench["configs"], bench["workloads"],
                  bench["end_to_end"] + bench["per_layer"]):
        ns = [e["name"] for e in group]
        need(len(ns) == len(set(ns)), f"a name twice among {ns}")
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    need(len(pairs) == len(set(pairs)), "a configuration and traffic twice")


def files(root):
    """Every file under ``benchmark/`` is named in the characters of a
    name and ``/``, and every ``.json`` parses. Returns the dotted names
    of its Python modules, for whoever can import them."""
    base = Path(root) / "benchmark"
    modules = []
    for p in sorted(base.rglob("*")):
        if "__pycache__" in p.parts or p.suffix == ".pyc":
            continue
        rel = p.relative_to(Path(root))
        need(FILE.match(str(rel)), f"{rel}: not a file name")
        if p.suffix == ".json":
            json.loads(p.read_text())
        if p.suffix == ".py" and p.stem != "__init__":
            modules.append(".".join(rel.with_suffix("").parts))
    return modules


def cut(key, said, published, here=None):
    """One entry of a configuration's ``cuts``: the reduced key ``key``
    of its source stands at ``here`` where the source has ``published``
    (``here`` None: no field of the program stands for it, as for a side
    module, and the entry's own word is taken). The floors are those of
    the ``model-configs`` guide's section 4."""
    need(isinstance(said, dict), f"{key}: in reduced, and no cuts entry")
    here = said.get("here") if here is None else here
    kind = said.get("kind")
    need(kind in KINDS, f"{key}: a cut is one of {KINDS}, not {kind!r}")
    need(said.get("published") == published and said.get("here") == here,
         f"{key}: the cut says {said.get('published')} -> "
         f"{said.get('here')}, the file {published} -> {here}")
    need(isinstance(here, int) and isinstance(published, int)
         and 0 <= here < published, f"{key}: {published} -> {here} cuts "
         f"nothing")
    line = said.get("deployment")
    need(isinstance(line, str) and line.strip() and "\n" not in line,
         f"{key}: a cut says in one line what deployment it stands for")
    if kind == "depth":
        lead, period = said.get("leading_dense"), said.get("period")
        need(isinstance(lead, int) and isinstance(period, int)
             and lead >= 0 and period >= 1,
             f"{key}: a cut in depth states leading_dense and period")
        need(here >= lead + max(4, period),
             f"{key}: {here} layers are under the {lead} leading dense "
             f"and {max(4, period)} that follow (a whole period of "
             f"{period}, and four at least)")
    elif kind == "experts":
        chips = said.get("shared_over_chips")
        need(isinstance(chips, int) and chips >= 1,
             f"{key}: a cut in experts states shared_over_chips, the "
             f"chips that share a layer")
        need(here >= 8, f"{key}: {here} experts held are under 8")
        need(here * chips == published,
             f"{key}: {here} experts on each of {chips} chips are not "
             f"the source's {published}")
    elif kind == "vocabulary":
        need(8 * here >= published,
             f"{key}: {here} rows are under an eighth of {published}")
    else:
        need(said.get("left_out") and said.get("why"),
             f"{key}: a module left out says what (left_out) and why")


def config(root, c):
    """One configuration of ``BENCHMARK.json`` against its file: the
    file is the ``TransformerConfig`` it runs as (at toy widths too),
    every field that the source publishes equals it unless the source's
    key is in ``reduced``, and what is in ``reduced`` is a cut that
    ``cut`` allows. A width is never one."""
    from deepspeed_tpu.models.transformer import TransformerConfig

    def held(ok, why):
        need(ok, f"{c['name']}: {why}")

    root = Path(root)
    held(c["file"] == f"benchmark/configs/{c['name']}.json", c["file"])
    f = json.loads((root / c["file"]).read_text())
    held(f["source"] == c["source"] and f["reduced"] == c["reduced"],
         "source and reduced mirror the file's")
    fields, pub = f["fields"], f["published"]
    TransformerConfig(**fields)                 # a file, no code
    TransformerConfig(**merge(fields, f.get("toy_fields", {})))
    for key in ("reference", "weights"):
        if key in f:
            mod = root / "benchmark" / (f[key].replace(".", "/") + ".py")
            held(mod.is_file(), f"{key} names {mod}")
    mapped = f.get("published_as", OPT_PUBLISHED_AS)
    held(set(OPT_PUBLISHED_AS) <= set(mapped),
         f"published_as maps at least {sorted(OPT_PUBLISHED_AS)}")
    reduced, cuts = f["reduced"], f.get("cuts", {})
    held(set(cuts) == set(reduced), f"cuts explains {sorted(cuts)}, "
         f"reduced lists {sorted(reduced)}")
    here = {}
    for field, key in mapped.items():
        held(field in fields and key in pub,
             f"published_as maps {field} to {key}")
        if key in reduced:
            held(not WIDTH.search(field),
                 f"{field} is a width, and never cut")
            here[key] = fields[field]
        else:
            held(fields[field] == pub[key],
                 f"{field} is {fields[field]}, the source's {key} is "
                 f"{pub[key]}, and reduced does not list it")
    for key in reduced:
        held(key in pub, f"reduced lists {key}, which published lacks")
        held(not WIDTH.search(key), f"{key} is a width, and never cut")
        # what stands for it here: the mapped field, else the file's own
        # top-level key of that name, else the entry's word
        cut(f"{c['name']}: {key}", cuts[key], pub[key],
            here.get(key, f.get(key)))


def configs(root):
    bench = read(root)
    named = {c["name"] for c in bench["configs"]}
    need(named == set(names(root, "configs")),
         "configs/ and BENCHMARK.json name the same configurations")
    need(named == {w["config"] for w in bench["workloads"]},
         "every configuration is used by some cell")
    for c in bench["configs"]:
        config(root, c)


def cell_files(root):
    bench = read(root)
    need(set(cells(bench)) == set(names(root, "workloads")),
         "workloads/ and BENCHMARK.json name the same cells")
    for w in bench["workloads"]:
        f = load(root, "workloads", w["name"])
        need(w["name"] == f"{w['config']}.{w['traffic']}", w["name"])
        for key in ("config", "traffic", "chips", "why"):
            need(f[key] == w[key], (w["name"], key))
        traffic = load(root, "traffic", f["traffic"])
        runner = Path(root) / "benchmark/runners" / f"{traffic['runner']}.py"
        need(runner.is_file(), runner)
        e2e = reported_by(bench, w["name"], "end_to_end")
        need("setup_s" in e2e and len(e2e) >= 2,
             f"{w['name']} reports setup_s and one more")
        need(reported_by(bench, w["name"], "per_layer"),
             f"{w['name']} reports at least one layer metric")
    need({w["traffic"] for w in bench["workloads"]}
         == set(names(root, "traffic")), "every traffic mix has a cell")


def reported_once(root):
    """``BENCHMARK.json`` says which metrics a cell reports
    (``run.reported_by``), the driver reads it there, and a cell file
    that said it again could disagree."""
    bench = read(root)
    for cell in cells(bench):
        need(not {"end_to_end", "per_layer"}
             & set(load(root, "workloads", cell)), cell)
        for kind in ("end_to_end", "per_layer"):
            need(reported_by(bench, cell, kind) == [
                m["name"] for m in bench[kind]
                if cell in reported_in(bench, m)], (cell, kind))
    need(all("workloads" in m for m in bench["per_layer"]),
         "a per-layer metric lists its cells")


def metric_files(root):
    bench = read(root)
    need({m["name"] for m in bench["per_layer"]}
         == set(names(root, "layer_metrics")),
         "layer_metrics/ and BENCHMARK.json name the same metrics")
    for m in bench["per_layer"]:
        f = load(root, "layer_metrics", m["name"])
        for key in ("unit", "better", "source", "layer", "moves"):
            need(f[key] == m[key], (m["name"], key))
        need((Path(root) / "benchmark/readers"
              / f"{f['reader']}.py").is_file(), (m["name"], f["reader"]))
        if m["name"].endswith("_roofline"):
            need(m["unit"] == "%", m["name"])
    need(all(m["source"] == "host_clock" for m in bench["end_to_end"]),
         "an end-to-end metric is the host clock's")


def moves(root):
    bench = read(root)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        need(m["moves"] in e2e and m["moves"] != "setup_s", m["name"])
        for cell in reported_in(bench, m):
            need(cell in reported_in(bench, e2e[m["moves"]]),
                 f"{m['name']} lists {cell}, which does not report "
                 f"{m['moves']}")


def layers(root):
    perf = (Path(root) / "PERF.md").read_text()
    for layer in {m["layer"] for m in read(root)["per_layer"]}:
        need(layer in perf, f"PERF.md's list of layers lacks {layer!r}")


RULES = (top_level, every_entry, unique_names, files, configs, cell_files,
         reported_once, metric_files, moves, layers)


def check(root):
    """Every rule on the checkout at ``root``."""
    for rule in RULES:
        rule(root)
