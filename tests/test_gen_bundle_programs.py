"""The serving builders' programs, op for op: every program the six
decoder builders make (``gen_lm``, ``hybrid_moe``, ``latent_moe``,
``latent_moe_sparse``, ``latent_moe_window``, ``latent_moe_streams``,
``block_moe``, ``window_moe``) is serialised and
its sha256 compared with ``tests/golden/gen_bundle_programs.json``.

* ``toy``: prefill (or chunk), decode and train program at the module's
  toy configuration.
* ``published``: prefill (or chunk) and decode at each serving
  configuration of ``benchmark/configs/``, read as its adapter's
  ``export`` reads it (the adapter runs, with the builder's exporter
  replaced by one that keeps its arguments).  Programs are BUILT, no
  startup runs: no weight is allocated.
* ``export``: one toy export a kind; ``gen_meta.json`` as parsed JSON,
  the two ``__model__`` files and the type and shape of what starts as
  zeros.

What is hashed is ``json.dumps(program.to_dict(), sort_keys=True)`` of
the main and the startup program: ops in order with inputs, outputs and
attributes, every variable's name, shape and type.  An op's
``creation_site`` is left out: it is the file and line of the CALLER
outside ``paddle_tpu`` (this file's absolute path here), not a part of
the program.  Every build runs under ``unique_name_scope("")`` so that
generated names start from zero whatever was built before.

The file uses public names only, so it passes unchanged on any commit
whose programs are these.  ``python tests/test_gen_bundle_programs.py``
rewrites the golden file from the tree it runs on.
"""

import hashlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")   # run as a script; the
#                                                 suite's conftest sets it

import paddle_tpu as fluid                              # noqa: E402
from paddle_tpu import models                           # noqa: E402
from paddle_tpu.framework import unique_name_scope      # noqa: E402
from paddle_tpu.models import (block_moe, gen_lm,       # noqa: E402
                               hybrid_moe, latent_moe, latent_moe_sparse,
                               latent_moe_streams, latent_moe_window,
                               window_moe)

GOLDEN = os.path.join(ROOT, "tests", "golden", "gen_bundle_programs.json")
TOY_SLOTS = 3

#: kind -> (module of the builders, toy configuration, exporter's name,
#: whether the prefill is a chunk program over the decode step's caches)
KINDS = {
    "gen_lm": (gen_lm, gen_lm.GenConfig, "export_gen_model", False),
    "hybrid_moe": (hybrid_moe, hybrid_moe.HybridConfig,
                   "export_hybrid_model", False),
    "latent_moe": (latent_moe, latent_moe.LatentMoEConfig,
                   "export_latent_model", True),
    "latent_moe_sparse": (latent_moe, latent_moe_sparse.SparseLatentConfig,
                          "export_latent_model", True),
    "latent_moe_window": (latent_moe, latent_moe_window.WindowLatentConfig,
                          "export_latent_model", True),
    "block_moe": (block_moe, block_moe.BlockMoEConfig,
                  "export_block_model", False),
    "window_moe": (window_moe, window_moe.WindowMoEConfig,
                   "export_window_model", True),
    "latent_moe_streams": (latent_moe,
                           latent_moe_streams.StreamsLatentConfig,
                           "export_latent_model", True),
}
#: published configuration -> its kind
PUBLISHED = {"genlm_opt6.7b": "gen_lm",
             "nemotron3_super_ep8": "hybrid_moe",
             "kimi_k2.6_text": "latent_moe",
             "sdar_30b_a3b_chat": "block_moe",
             "glm_5.2": "latent_moe",
             "mimo_v2_flash": "window_moe",
             "dots3_note_prev": "latent_moe_window",
             "xing4.0_29b_a4b": "latent_moe_streams"}

CASES = [("toy", kind, prog) for kind in KINDS
         for prog in ("prefill", "decode", "train")] \
    + [("published", name, prog) for name in PUBLISHED
       for prog in ("prefill", "decode")] \
    + [("export", kind, "bundle") for kind in KINDS]


def _stripped(program_dict):
    for block in program_dict["blocks"]:
        for op in block["ops"]:
            op.pop("creation_site", None)
    return program_dict


def _digest(*programs):
    text = json.dumps([_stripped(p if isinstance(p, dict) else p.to_dict())
                       for p in programs], sort_keys=True)
    return hashlib.sha256(text.encode()).hexdigest()


def _geometry(hp, num_slots, page_len):
    """The pool an exporter makes when it is not told ``num_pages``."""
    page_len = max(1, min(int(page_len), int(hp.max_len)))
    return page_len, int(num_slots) * -(-int(hp.max_len) // page_len)


def _serving_program(kind, prog, hp, num_slots, page_len):
    module, _, _, chunked = KINDS[kind]
    page_len, num_pages = _geometry(hp, num_slots, page_len)
    main, startup = fluid.Program(), fluid.Program()
    with unique_name_scope(""), fluid.program_guard(main, startup):
        if prog == "decode":
            module.build_paged_decode_program(hp, num_slots, page_len,
                                              num_pages)
        elif chunked:
            module.build_chunk_program(hp, num_slots, page_len, num_pages)
        else:
            module.build_prefill_program(hp)
    return _digest(main, startup)


def _published_arguments(name, monkeypatch):
    """``(hp, num_slots, page_len)`` as the configuration's adapter hands
    them to the builder's exporter."""
    from lib import models as adapters
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    module, _, exporter, _ = KINDS[PUBLISHED[name]]
    kept = {}

    def keep(path, hp, num_slots=8, page_len=gen_lm.PAGE_LEN_DEFAULT, **kw):
        kept.update(hp=hp, num_slots=num_slots, page_len=page_len)

    monkeypatch.setattr(module, exporter, keep)
    adapters.adapter_of(cfg).export("unused", cfg)
    return kept["hp"], kept["num_slots"], kept["page_len"]


def _export(kind, dirname):
    module, config, exporter, _ = KINDS[kind]
    with unique_name_scope(""):
        getattr(module, exporter)(dirname, config(), num_slots=TOY_SLOTS)
    with open(os.path.join(dirname, gen_lm.META_FILENAME)) as f:
        meta = json.load(f)
    out = {"meta": meta}
    for part in ("prefill", "decode"):
        with open(os.path.join(dirname, part, "__model__")) as f:
            model = json.load(f)
        out[part] = {"feeds": model["feed_var_names"],
                     "fetches": model["fetch_var_names"],
                     "sha256": _digest(model["program"])}
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.io.load_inference_model(os.path.join(dirname, "decode"),
                                      fluid.Executor())
        out["zeros"] = {}
        for name in meta["cache_vars"] + meta.get("state_vars", []):
            value = scope.find_var(name)
            assert not value.any(), name
            out["zeros"][name] = [str(value.dtype), list(value.shape)]
    return out


def _observe(group, name, prog, monkeypatch, tmp_path):
    if group == "export":
        return _export(name, str(tmp_path / name))
    if group == "published":
        hp, slots, page_len = _published_arguments(name, monkeypatch)
        return _serving_program(PUBLISHED[name], prog, hp, slots, page_len)
    if prog == "train":
        with unique_name_scope(""):
            main, startup, _, _ = models.build_train_program(name)
        return _digest(main, startup)
    return _serving_program(name, prog, KINDS[name][1](), TOY_SLOTS,
                            gen_lm.PAGE_LEN_DEFAULT)


@pytest.mark.parametrize("group,name,prog", CASES,
                         ids=["-".join(c) for c in CASES])
def test_the_program_is_the_recorded_one(group, name, prog, monkeypatch,
                                         tmp_path):
    with open(GOLDEN) as f:
        golden = json.load(f)
    seen = _observe(group, name, prog, monkeypatch, tmp_path)
    assert seen == golden["-".join((group, name, prog))]


if __name__ == "__main__":
    import pathlib
    import tempfile

    recorded = {}
    for case in CASES:
        with tempfile.TemporaryDirectory() as tmp:
            patch = pytest.MonkeyPatch()
            try:
                recorded["-".join(case)] = _observe(
                    *case, patch, pathlib.Path(tmp))
            finally:
                patch.undo()
    with open(GOLDEN, "w") as f:
        json.dump(recorded, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"{len(recorded)} cases -> {GOLDEN}")
