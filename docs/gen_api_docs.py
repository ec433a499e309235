"""Generate the API reference (docs/api/*.md) from the live package.

The reference shipped sphinx API docs (docs/source/*.rst built in
.travis.yml:9-12); this environment has no sphinx, so a small introspection
generator produces the same artifact class: one page per public module with
every public class/function signature + docstring. CI runs ``--check`` to
fail when the generated pages drift from the code.

Usage:
    python docs/gen_api_docs.py          # (re)write docs/api/
    python docs/gen_api_docs.py --check  # exit 1 if docs/api/ is stale
"""

import importlib
import inspect
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

#: the public surface, in reading order
MODULES = [
    "tensorflowonspark_tpu",
    "tensorflowonspark_tpu.TFCluster",
    "tensorflowonspark_tpu.elastic",
    "tensorflowonspark_tpu.TFSparkNode",
    "tensorflowonspark_tpu.TFNode",
    "tensorflowonspark_tpu.TFManager",
    "tensorflowonspark_tpu.TFParallel",
    "tensorflowonspark_tpu.reservation",
    "tensorflowonspark_tpu.registry",
    "tensorflowonspark_tpu.pipeline",
    "tensorflowonspark_tpu.dfutil",
    "tensorflowonspark_tpu.tfrecord",
    "tensorflowonspark_tpu.native_io",
    "tensorflowonspark_tpu.tpu_info",
    "tensorflowonspark_tpu.marker",
    "tensorflowonspark_tpu.shm",
    "tensorflowonspark_tpu.serving",
    "tensorflowonspark_tpu.serving_mesh",
    "tensorflowonspark_tpu.compat",
    "tensorflowonspark_tpu.util",
    "tensorflowonspark_tpu.resilience",
    "tensorflowonspark_tpu.control",
    "tensorflowonspark_tpu.control.core",
    "tensorflowonspark_tpu.control.scaler",
    "tensorflowonspark_tpu.chaos",
    "tensorflowonspark_tpu.obs",
    "tensorflowonspark_tpu.obs.registry",
    "tensorflowonspark_tpu.obs.aggregate",
    "tensorflowonspark_tpu.obs.exporter",
    "tensorflowonspark_tpu.obs.trace",
    "tensorflowonspark_tpu.obs.tracing",
    "tensorflowonspark_tpu.obs.flight",
    "tensorflowonspark_tpu.obs.tracemerge",
    "tensorflowonspark_tpu.parallel.mesh",
    "tensorflowonspark_tpu.parallel.sharding",
    "tensorflowonspark_tpu.parallel.collectives",
    "tensorflowonspark_tpu.parallel.ring_attention",
    "tensorflowonspark_tpu.parallel.pipeline_parallel",
    "tensorflowonspark_tpu.train.strategy",
    "tensorflowonspark_tpu.train.checkpoint",
    "tensorflowonspark_tpu.ckpt",
    "tensorflowonspark_tpu.ckpt.engine",
    "tensorflowonspark_tpu.ckpt.snapshot",
    "tensorflowonspark_tpu.ckpt.manifest",
    "tensorflowonspark_tpu.ckpt.reshard",
    "tensorflowonspark_tpu.train.export",
    "tensorflowonspark_tpu.train.metrics",
    "tensorflowonspark_tpu.data.loader",
    "tensorflowonspark_tpu.data.autotune",
    "tensorflowonspark_tpu.data.decode_plane",
    "tensorflowonspark_tpu.data.tokenizer",
    "tensorflowonspark_tpu.data.text_plane",
    "tensorflowonspark_tpu.data.imagenet",
    "tensorflowonspark_tpu.data.cifar",
    "tensorflowonspark_tpu.models.decoder",
    "tensorflowonspark_tpu.models.mnist",
    "tensorflowonspark_tpu.models.resnet",
    "tensorflowonspark_tpu.models.segmentation",
    "tensorflowonspark_tpu.models.transformer",
    "tensorflowonspark_tpu.ops.flash_attention",
    "tensorflowonspark_tpu.ops.flash_blocks",
    "tensorflowonspark_tpu.ops.fused_bn",
    "tensorflowonspark_tpu.ops.grouped_matmul",
    "tensorflowonspark_tpu.ops.hyper_connection",
    "tensorflowonspark_tpu.ops.moe_combine",
    "tensorflowonspark_tpu.ops.selective_scan",
    "tensorflowonspark_tpu.ops.ssd_scan",
    "tensorflowonspark_tpu.backends",
    "tensorflowonspark_tpu.backends.local",
    "tosa",
    "tosa.core",
]


def _strip_addresses(text):
    """Default-value / docstring reprs with memory addresses are
    run-dependent; docs must be deterministic for the CI freshness check."""
    import re

    text = re.sub(r"<([\w.]+) object at 0x[0-9a-f]+>", r"<\1>", text)
    return re.sub(r"<(function|built-in function) ([\w.<>]+) at 0x[0-9a-f]+>", r"<\1 \2>", text)


def _signature(obj):
    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    return _strip_addresses(sig)


def _doc(obj):
    if inspect.isclass(obj):
        # the class's OWN docstring only: inspect.getdoc inherits the
        # base's, which would duplicate a mixin-base docstring under every
        # docstring-less subclass heading
        doc = inspect.cleandoc(vars(obj).get("__doc__") or "")
    else:
        doc = inspect.getdoc(obj) or ""
    return _strip_addresses(doc)


def _is_public(name, obj, module):
    if name.startswith("_"):
        return False
    mod = getattr(obj, "__module__", None)
    return mod == module.__name__  # skip re-exports; they render at home


def _render_function(name, fn, heading):
    lines = ["{} `{}{}`".format(heading, name, _signature(fn)), ""]
    doc = _doc(fn)
    if doc:
        lines += [doc, ""]
    return lines


def _render_class(name, cls):
    lines = ["## class `{}{}`".format(name, _signature(cls)), ""]
    doc = _doc(cls)
    if doc:
        lines += [doc, ""]
    for mname, member in sorted(vars(cls).items()):
        if mname.startswith("_") and mname != "__call__":
            continue
        fn = member.__func__ if isinstance(member, (classmethod, staticmethod)) else member
        if callable(fn) and not inspect.isclass(fn):
            mdoc = _doc(fn)
            lines.append("### `{}.{}{}`".format(name, mname, _signature(fn)))
            lines.append("")
            if mdoc:
                lines += [mdoc, ""]
        elif isinstance(member, property):
            lines.append("### property `{}.{}`".format(name, mname))
            lines.append("")
            mdoc = _doc(member)
            if mdoc:
                lines += [mdoc, ""]
    return lines


def render_module(modname):
    module = importlib.import_module(modname)
    lines = ["# `{}`".format(modname), ""]
    doc = _doc(module)
    if doc:
        lines += [doc, ""]
    classes, functions, constants = [], [], []
    for name, obj in sorted(vars(module).items()):
        if not _is_public(name, obj, module) and not (
            not name.startswith("_") and not callable(obj) and not inspect.ismodule(obj)
        ):
            continue
        if inspect.isclass(obj) and obj.__module__ == modname:
            classes.append((name, obj))
        elif inspect.isfunction(obj) and obj.__module__ == modname:
            functions.append((name, obj))
        elif (
            not name.startswith("_")
            and isinstance(obj, (int, float, str, bytes, tuple))
            and not inspect.ismodule(obj)
        ):
            constants.append((name, obj))
    if constants:
        lines.append("## Constants")
        lines.append("")
        for name, val in constants:
            rep = repr(val)
            if len(rep) > 80:
                rep = rep[:77] + "..."
            lines.append("- `{} = {}`".format(name, rep))
        lines.append("")
    for name, fn in functions:
        lines += _render_function(name, fn, "## ")
    for name, cls in classes:
        lines += _render_class(name, cls)
    return "\n".join(lines).rstrip() + "\n"


def main(argv):
    check = "--check" in argv
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "api")
    os.makedirs(out_dir, exist_ok=True)
    index = [
        "# API reference",
        "",
        "Generated by `docs/gen_api_docs.py` from the live package "
        "(`python docs/gen_api_docs.py` to refresh; CI checks freshness).",
        "",
    ]
    stale = []
    for modname in MODULES:
        content = render_module(modname)
        fname = modname.replace("tensorflowonspark_tpu", "tos_tpu").replace(".", "_") + ".md"
        path = os.path.join(out_dir, fname)
        index.append("- [`{}`]({})".format(modname, fname))
        old = open(path).read() if os.path.isfile(path) else None
        if old != content:
            if check:
                stale.append(fname)
            else:
                with open(path, "w") as f:
                    f.write(content)
    index_text = "\n".join(index) + "\n"
    index_path = os.path.join(out_dir, "index.md")
    old_index = open(index_path).read() if os.path.isfile(index_path) else None
    if old_index != index_text:
        if check:
            stale.append("index.md")
        else:
            with open(index_path, "w") as f:
                f.write(index_text)
    if check and stale:
        print("stale API docs (run python docs/gen_api_docs.py): {}".format(stale))
        return 1
    print("API docs {} in {}".format("checked" if check else "written", out_dir))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
