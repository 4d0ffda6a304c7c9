"""Env utilities: stream cleanup, fault tolerance, platform introspection.

Reference parity (SURVEY.md §2.1 row "Env/utilities":
UPSTREAM:.../core/env/{StreamUtilities,EnvironmentUtils,
FaultToleranceUtils}.scala): ``StreamUtilities.using`` (close-on-exit
resource scoping), ``FaultToleranceUtils.retryWithTimeout`` (bounded
retries around flaky cluster operations — the reference wraps its driver
rendezvous and HTTP calls in it), and ``EnvironmentUtils`` (cluster/
platform introspection).  Same contracts, accelerator-flavored."""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Iterable, Optional, TypeVar

T = TypeVar("T")


@contextlib.contextmanager
def using(*resources):
    """Scala ``StreamUtilities.using``: yield resources, close them all on
    exit (even on error).  A close failure is raised only when the body
    itself succeeded — a body exception always propagates unmasked (the
    reference's semantics)."""
    body_failed = False
    try:
        yield resources if len(resources) != 1 else resources[0]
    except BaseException:
        body_failed = True
        raise
    finally:
        err = None
        for r in resources:
            for meth in ("close", "stop", "shutdown"):
                fn = getattr(r, meth, None)
                if callable(fn):
                    try:
                        fn()
                    except Exception as e:  # keep closing the rest
                        err = err or e
                    break
        if err is not None and not body_failed:
            raise err


class FaultToleranceUtils:
    """Bounded retry with per-attempt timeout (reference
    ``FaultToleranceUtils.retryWithTimeout``)."""

    @staticmethod
    def retry_with_timeout(
        fn: Callable[[], T],
        timeout_s: float = 60.0,
        retries: int = 3,
        backoff_s: float = 0.5,
        retry_on: tuple = (Exception,),
    ) -> T:
        """Run ``fn`` with at most ``retries`` attempts; each attempt is
        abandoned after ``timeout_s`` (the worker thread is left to die —
        Python cannot kill threads, matching the reference's Future-based
        abandon semantics)."""
        last: Optional[BaseException] = None
        for attempt in range(max(1, retries)):
            result: dict = {}
            done = threading.Event()

            def run():
                try:
                    result["value"] = fn()
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    result["error"] = e
                finally:
                    done.set()

            t = threading.Thread(target=run, daemon=True)
            t.start()
            if not done.wait(timeout_s):
                last = TimeoutError(
                    f"attempt {attempt + 1}/{retries} exceeded {timeout_s}s"
                )
            elif "error" in result:
                if not isinstance(result["error"], retry_on):
                    raise result["error"]
                last = result["error"]
            else:
                return result["value"]
            if attempt + 1 < retries:
                time.sleep(backoff_s * (2**attempt))
        raise last if last is not None else RuntimeError("retry failed")


# Spark-flavored alias (the reference API name)
retryWithTimeout = FaultToleranceUtils.retry_with_timeout


class EnvironmentUtils:
    """Platform introspection (reference ``EnvironmentUtils``), accelerator
    edition: device counts/kinds instead of executor cores."""

    @staticmethod
    def platform() -> str:
        import jax

        return jax.default_backend()

    @staticmethod
    def num_devices() -> int:
        import jax

        return jax.device_count()

    @staticmethod
    def num_processes() -> int:
        import jax

        return jax.process_count()

    @staticmethod
    def device_kinds() -> list:
        import jax

        return sorted({d.device_kind for d in jax.devices()})

    @staticmethod
    def summary() -> dict:
        import jax

        return {
            "platform": jax.default_backend(),
            "devices": jax.device_count(),
            "local_devices": len(jax.local_devices()),
            "processes": jax.process_count(),
            "device_kinds": EnvironmentUtils.device_kinds(),
        }


def refuse_child_on_held_chip(what: str) -> None:
    """One process per chip.  An accelerator belongs to the process that
    first touched JAX; a child that needs it then fails or hangs.  Entry
    points that start JAX-needing children call this first: it raises when
    THIS process has already brought up a non-CPU backend (merely having
    imported jax holds nothing)."""
    import sys

    if "jax" not in sys.modules:
        return
    import jax
    # no public "is a backend up?" query — jax.default_backend() itself
    # would bring one up and take the chip
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized() and jax.default_backend() != "cpu":
        raise RuntimeError(
            f"{what}: this process already holds the "
            f"{jax.default_backend()} device(s), so a child process that "
            "needs them would fail or hang.  Start children from a parent "
            "that never touches JAX (train and save in a child too), or "
            "serve N in-process apps on N devices — see "
            "mmlspark_tpu/serve/README.md, \"One process per chip\"."
        )
