"""``ds_report`` — environment and op-compatibility report.

Reference ``deepspeed/env_report.py``: prints the installed-ops compatibility
matrix, torch/cuda versions and nvcc availability. The TPU analog reports the
JAX stack, the device platform/mesh, the native (C++) op build status and the
Pallas availability of each registered op.

Run: ``python -m deepspeed_tpu.env_report``
"""

import shutil
import subprocess
import sys

GREEN = "\033[92m"
RED = "\033[91m"
YELLOW = "\033[93m"
END = "\033[0m"
OKAY = f"{GREEN}[OKAY]{END}"
NO = f"{YELLOW}[NO]{END}"
FAIL = f"{RED}[FAIL]{END}"


def software_report():
    rows = []
    for mod in ("jax", "jaxlib", "flax", "optax", "numpy", "orbax.checkpoint"):
        try:
            m = __import__(mod)
            rows.append((mod, getattr(m, "__version__", "unknown"), OKAY))
        except ImportError:
            rows.append((mod, "-", NO))
    rows.append(("python", sys.version.split()[0], OKAY))
    gxx = shutil.which("g++")
    if gxx:
        try:
            v = subprocess.run(["g++", "--version"], capture_output=True,
                               text=True, timeout=10).stdout.splitlines()[0]
        except Exception:
            v = "unknown"
        rows.append(("g++ (native ops)", v, OKAY))
    else:
        rows.append(("g++ (native ops)", "-", NO))
    return rows


def hardware_report():
    """What ``jax.devices()`` returns in this process."""
    rows = []
    try:
        import jax
        devs = jax.devices()
        plat = devs[0].platform if devs else "none"
        rows.append(("platform", plat, OKAY))
        rows.append(("device count", str(len(devs)), OKAY))
        rows.append(("devices", ", ".join(str(d) for d in devs[:8])
                     + (" ..." if len(devs) > 8 else ""), OKAY))
        stats = devs[0].memory_stats()
        if stats:
            rows.append(("hbm bytes_limit",
                         str(stats.get("bytes_limit", "n/a")), OKAY))
        rows.append(("process count", str(jax.process_count()), OKAY))
    except Exception as e:
        rows.append(("jax devices", f"error: {e}", FAIL))
    return rows


def ops_report():
    from deepspeed_tpu.ops.registry import available_ops, get_op_builder
    rows = []
    for name in available_ops():
        builder = get_op_builder(name)()
        try:
            compatible = builder.is_compatible()
            impl = "pallas/native" if compatible else "pure-XLA fallback"
            rows.append((name, impl, OKAY if compatible else NO))
        except Exception as e:
            rows.append((name, f"error: {e}", FAIL))
    for native in ("ds_aio", "ds_cpu_adam"):
        from deepspeed_tpu.ops.native import load_native
        lib = load_native(native)
        rows.append((f"native/{native}",
                     "built" if lib is not None else "fallback",
                     OKAY if lib is not None else NO))
    return rows


def _print_table(title, rows):
    print("-" * 70)
    print(title)
    print("-" * 70)
    for name, info, status in rows:
        print(f"{name:.<32} {status} {info}")


def main(hide_operator_status=False, hide_errors_and_warnings=False):
    def clean(rows):
        return [r for r in rows if FAIL not in r[2]] \
            if hide_errors_and_warnings else rows

    print("DeepSpeed-TPU C++/Pallas op report")
    if not hide_operator_status:
        _print_table("op compatibility", clean(ops_report()))
    _print_table("software", clean(software_report()))
    _print_table("hardware", clean(hardware_report()))
    return 0


def cli_main():
    sys.exit(main())


if __name__ == "__main__":
    cli_main()
