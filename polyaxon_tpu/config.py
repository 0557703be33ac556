"""Client/deployment configuration: env layering + home config.

Parity: reference ``ClientConfig`` / env vars / home managers
(SURVEY.md 2.15/5.6; expected at ``polyaxon/_env_vars``, ``_managers/``
— unverified).  Layering, lowest to highest precedence:

    1. defaults
    2. home config file (``$POLYAXON_TPU_HOME/config.json``)
    3. ``POLYAXON_TPU_*`` environment variables
    4. explicit constructor kwargs

TPU additions: default mesh/topology settings (slice type, strategy
axes) ride the same config so a deployment can pin them fleet-wide.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

ENV_PREFIX = "POLYAXON_TPU_"

_ENV_KEYS = {
    "host": "HOST",
    "token": "AUTH_TOKEN",
    "project": "PROJECT",
    "namespace": "NAMESPACE",
    "timeout": "TIMEOUT",
    "verify_ssl": "VERIFY_SSL",
    "debug": "DEBUG",
    "default_slice_type": "DEFAULT_SLICE_TYPE",
    "default_strategy": "DEFAULT_STRATEGY",
    "connections_file": "CONNECTIONS_FILE",
}

_BOOLS = {"verify_ssl", "debug"}
_FLOATS = {"timeout"}
_JSON = {"default_strategy"}


def home_dir() -> str:
    from .client.store import default_home

    return default_home()


def enable_compilation_cache(names_in_key: bool = False) -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Every entry point that compiles (``train``, ``ptpu serve``, ``ptpu
    generate``, ``bench.py``) calls this before its first compile, so
    tuner trials, gang restarts and a server's next start re-read the
    programs the last process compiled.

    The directory is placed from OUTSIDE.  With
    ``JAX_COMPILATION_CACHE_DIR`` set, JAX has read it itself and
    nothing is set in code (its other cache knobs are the outside's
    too).  Otherwise it is ``<checkout>/.jax_cache`` — never a path
    built from a temp name, a pid, the time or ``POLYAXON_TPU_HOME``:
    a directory that moves never hits.

    ``names_in_key``: for a process that will take a device trace
    (``train --profile-at``, ``ptpu serve --profile-dir``).  The cache's
    key leaves a program's metadata out, so an executable read from it
    carries the NAMES of whichever tree compiled it — name stacks,
    files, lines — and a device trace shows those: the scopes of
    ``spans.py`` as they stood then, or none (v5e, PERF.md section 6,
    PR 37: a server's prefill programs read from a cache that the
    parent commit had filled showed no scope while the decode program,
    compiled in the process, showed them all).  A traced process
    therefore keys the cache by the metadata too: it compiles what no
    process of THIS tree has compiled for a trace before, and reads
    back what one has.  Untraced processes keep the key they had.
    """
    import jax

    if names_in_key:
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", True)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache_dir:
        return cache_dir

    cache_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Persist even sub-second compiles: tiny sweep trials are exactly
    # the repeated-compile workload.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


@contextlib.contextmanager
def fresh_compile():
    """Programs first called inside are compiled in this process, not
    read from (or written to) the persistent compilation cache.

    For a program whose results carry a PINNED device layout that is
    not the device's default.  An executable that comes back from the
    cache hands out its results labelled with the default layout,
    whatever it was compiled to write (v5e, jax 0.9.0: PERF.md section
    6, PR 28): the next program either refuses the array — when it pins
    the layout of its argument, as every program here does — or reads
    its bytes in the wrong order.  Compiled here, the label is right.

    JAX decides once whether it uses the cache and remembers it, so
    turning the switch takes ``reset_cache()`` with it, on the way in
    and on the way out (the cache is opened again by the next compile
    that wants it).  The switch is the process's: a thread that
    compiles meanwhile skips the cache once too, which is harmless."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    def switch(on: bool) -> None:
        jax.config.update("jax_enable_compilation_cache", on)
        compilation_cache.reset_cache()

    was = jax.config.jax_enable_compilation_cache
    switch(False)
    try:
        yield
    finally:
        switch(was)


def _config_path() -> str:
    return os.path.join(home_dir(), "config.json")


def _write_config(path: str, payload: Dict[str, Any]) -> None:
    """Atomic write, 0600: config.json may hold the API bearer token, so
    it must not be readable by other local users (ADVICE r1)."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
    with os.fdopen(fd, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    os.replace(tmp, path)
    os.chmod(path, 0o600)


def _coerce(key: str, value: Any) -> Any:
    if value is None or not isinstance(value, str):
        return value
    if key in _BOOLS:
        return value.lower() in ("1", "true", "yes", "on")
    if key in _FLOATS:
        return float(value)
    if key in _JSON:
        try:
            return json.loads(value)
        except ValueError:
            return value
    return value


@dataclass
class ClientConfig:
    host: Optional[str] = None
    token: Optional[str] = None
    project: str = "default"
    namespace: str = "polyaxon-tpu"
    timeout: float = 30.0
    verify_ssl: bool = True
    debug: bool = False
    # TPU-wide defaults
    default_slice_type: str = "v5litepod-8"
    default_strategy: Dict[str, int] = field(default_factory=dict)
    connections_file: Optional[str] = None

    @staticmethod
    def read_file_layer() -> Dict[str, Any]:
        """Raw key -> value pairs persisted in the home config file."""
        path = _config_path()
        if not os.path.exists(path):
            return {}
        try:
            with open(path) as f:
                stored = json.load(f)
            return {k: v for k, v in stored.items() if k in _ENV_KEYS}
        except (OSError, ValueError):
            return {}

    @classmethod
    def load(cls, **overrides: Any) -> "ClientConfig":
        """Apply the full layering."""
        values: Dict[str, Any] = dict(cls.read_file_layer())
        for key, suffix in _ENV_KEYS.items():
            env_val = os.environ.get(ENV_PREFIX + suffix)
            if env_val is not None:
                values[key] = _coerce(key, env_val)
        values.update({k: v for k, v in overrides.items()
                       if v is not None})
        return cls(**values)

    def save(self) -> str:
        """Persist to the home config file (the `config set` surface)."""
        path = _config_path()
        payload = {k: v for k, v in dataclasses.asdict(self).items()
                   if v not in (None, {}, [])}
        _write_config(path, payload)
        return path

    @classmethod
    def set_file_values(cls, pairs: Dict[str, str]) -> str:
        """Mutate ONLY the file layer: never freeze env values or
        package defaults into config.json (a stale exported token/host
        must not outlive its shell)."""
        stored = cls.read_file_layer()
        for key, raw in pairs.items():
            if key not in _ENV_KEYS:
                raise KeyError(
                    f"Unknown config key {key!r}; known: "
                    f"{sorted(_ENV_KEYS)}")
            stored[key] = _coerce(key, raw)
        path = _config_path()
        _write_config(path, stored)
        return path

    @classmethod
    def unset_file_values(cls, keys) -> str:
        """Remove keys from the file layer (atomic write)."""
        stored = cls.read_file_layer()
        for key in keys:
            stored.pop(key, None)
        path = _config_path()
        _write_config(path, stored)
        return path

    def set_value(self, key: str, raw: str) -> None:
        if key not in _ENV_KEYS:
            raise KeyError(
                f"Unknown config key {key!r}; known: {sorted(_ENV_KEYS)}")
        setattr(self, key, _coerce(key, raw))

    @property
    def in_cluster(self) -> bool:
        return bool(os.environ.get(ENV_PREFIX + "RUN_UUID"))
