"""Environment helpers: the sanctioned home for ad-hoc ``DFT_*`` reads,
plus the child environment for CPU-only virtual-mesh runs.

Knob reads (``env_flag`` / ``env_int`` / ``env_float`` / ``env_str``):
every ``DFT_*`` knob that does not ride an ``_EnvCfg`` schema
(utils/config.py) must be read through these helpers — graftlint's
``env-knob-drift`` checker flags raw ``os.environ``/``getenv`` reads of
``DFT_*`` names anywhere else, and cross-checks the knob names collected
here (literal first arguments) against the knob reference table in
docs/OPERATIONS.md. The boolean coercion convention matches
``_EnvCfg.from_env`` exactly ('0'/'false'/'False'/'' are False), so the
two read paths cannot drift.

Compile cache (``place_compile_cache``): the one rule for where jax's
persistent compilation cache lives, called by every entry point before its
first compile.

CPU child environment (``scrubbed_cpu_env``): the virtual-mesh dry run
(``__graft_entry__.dryrun_multichip``) and the CPU-only bench helpers pin a
child process to the host platform with a forced device count; the rule
lives here once.
"""

import os

_FALSY = ("0", "false", "False", "")


def env_flag(name: str, default: bool) -> bool:
    """Boolean knob: unset -> ``default``; else the one _EnvCfg coercion
    convention ('0'/'false'/'False'/'' are False, anything else True)."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw not in _FALSY


def env_int(name: str, default=None):
    """Integer knob: unset or empty -> ``default`` (which may be None for
    caller-computed fallbacks, e.g. cpu-count-derived pool sizes)."""
    raw = os.environ.get(name)
    if raw in (None, ""):
        return default
    return int(raw)


def env_float(name: str, default=None):
    """Float knob: unset or empty -> ``default``."""
    raw = os.environ.get(name)
    if raw in (None, ""):
        return default
    return float(raw)


def env_str(name: str, default=None):
    """String knob: unset or empty -> ``default``."""
    raw = os.environ.get(name)
    if raw in (None, ""):
        return default
    return raw


def place_compile_cache() -> str:
    """Place jax's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax's own reading of it
    stands and no directory is set here. Where it is not, the cache is
    ``<checkout>/.jax_cache``, computed from this package's location: the
    path is part of what a later process must reproduce to hit the cache,
    so it is never a temporary name, a pid or the time. Every program is
    cached however quickly it compiled (jax's default skips those under a
    second, which would leave a warm start still compiling, and writing
    entries for whatever crossed the second this time). Call before the
    first compile (config only — no backend is created)."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    package_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(os.path.dirname(package_dir), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def scrubbed_cpu_env(n_devices=None, extra_pythonpath=None):
    """Return an env dict that pins a child's jax to the host CPU platform:
    ``JAX_PLATFORMS=cpu``; with ``n_devices``, that many virtual host
    devices via ``XLA_FLAGS`` (replacing any existing device-count flag);
    ``extra_pythonpath`` prepended to ``PYTHONPATH``."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    if n_devices is not None:
        flags = [
            f
            for f in env.get("XLA_FLAGS", "").split()
            if "host_platform_device_count" not in f
        ]
        flags.append(f"--xla_force_host_platform_device_count={max(int(n_devices), 1)}")
        env["XLA_FLAGS"] = " ".join(flags)
    if extra_pythonpath:
        pyp = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        env["PYTHONPATH"] = os.pathsep.join([extra_pythonpath] + pyp)
    return env
