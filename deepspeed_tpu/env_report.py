"""Environment / capability report — the ``ds_report`` analogue
(reference ``deepspeed/env_report.py``): instead of probing CUDA op
builders, reports the JAX/TPU stack and which framework features are
usable in this environment."""

import importlib
import os
import sys


GREEN_OK = "\033[92m[OK]\033[0m"
RED_NO = "\033[91m[NO]\033[0m"


def _try_import(name):
    try:
        mod = importlib.import_module(name)
        return getattr(mod, "__version__", "unknown")
    except Exception:
        return None


def collect_report() -> dict:
    import deepspeed_tpu

    report = {
        "deepspeed_tpu": deepspeed_tpu.__version__,
        "python": sys.version.split()[0],
        "packages": {},
        "devices": [],
        "platform": None,
        "features": {},
    }
    for pkg in ("jax", "jaxlib", "flax", "optax", "orbax.checkpoint", "numpy"):
        report["packages"][pkg] = _try_import(pkg)

    # A backend that cannot start raises here: a report that printed
    # "unavailable" and went on to list features would hide the failure.
    import jax

    from deepspeed_tpu.ops.kernel_cases import kernel_cases
    from deepspeed_tpu.profiling.flops_profiler import TPU_PEAK_TFLOPS
    from deepspeed_tpu.utils.compile_cache import (CACHE_DIR_ENV,
                                                   DEFAULT_CACHE_DIR)

    dev = jax.devices()[0]
    report["platform"] = dev.platform
    report["device_kind"] = dev.device_kind
    report["device_count"] = len(jax.devices())
    report["devices"] = [str(d) for d in jax.devices()]
    report["process_count"] = jax.process_count()
    # Is there a published peak for this chip? Without one no MFU,
    # roofline verdict or bench row is reported (flops_profiler).
    report["device_kind_in_peak_table"] = dev.device_kind in TPU_PEAK_TFLOPS
    # The compile-cache directory in force for the repo's entry scripts
    # (utils/compile_cache.py: the env var if set, else the in-checkout
    # default). Read, not configured: a report changes nothing.
    report["compile_cache_dir"] = (
        os.environ.get(CACHE_DIR_ENV) or DEFAULT_CACHE_DIR)

    # How the Pallas kernels DISPATCH here — not whether they work: that
    # a kernel compiles for the chip is established by
    # tests/test_tpu_lowering.py (chipless v5e compile) and that it is
    # right there by chip_smoke.py, not by the platform string.
    report["pallas_dispatch"] = (
        "mosaic (compiled for the TPU)" if dev.platform == "tpu"
        else "interpreter (off-TPU: correctness only)")
    report["pallas_kernels"] = [case.name for case in kernel_cases()]
    report["features"] = {
        "xla_reference_ops": report["packages"]["jax"] is not None,
        "multihost (jax.distributed)": report["packages"]["jax"] is not None,
        "zero_stages_0_3": True,
        "pipeline_parallelism": True,
        "sequence_parallelism (ring/ulysses)": True,
        "onebit_optimizers": True,
    }
    from deepspeed_tpu.ops.registry import list_ops

    report["ops"] = {name: spec.available()
                     for name, spec in sorted(list_ops().items())}
    return report


def main():
    report = collect_report()
    print("-" * 60)
    print("DeepSpeed-TPU environment report")
    print("-" * 60)
    print(f"deepspeed_tpu .......... {report['deepspeed_tpu']}")
    print(f"python ................. {report['python']}")
    for pkg, ver in report["packages"].items():
        mark = GREEN_OK if ver else RED_NO
        print(f"{pkg:22s} {mark} {ver or 'not installed'}")
    print(f"platform ............... {report['platform']}")
    print(f"device kind ............ {report['device_kind']} "
          f"x {report['device_count']}")
    in_table = report["device_kind_in_peak_table"]
    print(f"in peak tables ......... {GREEN_OK if in_table else RED_NO} "
          f"{'MFU/roofline reported' if in_table else 'no MFU/roofline'}")
    print(f"compile cache .......... {report['compile_cache_dir']}")
    for d in report["devices"]:
        print(f"  device: {d}")
    print("-" * 60)
    print(f"pallas kernels dispatch to: {report['pallas_dispatch']}")
    for k in report["pallas_kernels"]:
        print(f"  {k}")
    print("-" * 60)
    print("feature availability")
    for feat, ok in report["features"].items():
        print(f"  {GREEN_OK if ok else RED_NO} {feat}")
    print("-" * 60)
    print("op registry (op_builder analogue)")
    for name, ok in report["ops"].items():
        print(f"  {GREEN_OK if ok else RED_NO} {name}")
    print("-" * 60)


if __name__ == "__main__":
    main()
