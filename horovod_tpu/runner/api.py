"""Programmatic launcher: ``horovod_tpu.runner.run(fn, ...)``.

Reference: ``horovod/runner/__init__.py:90`` — pickle ``fn`` with
cloudpickle, launch the distributed job, collect and return the per-rank
return values (tested by ``test/test_interactiverun.py``).  The function
travels and the results return over the launcher's HMAC-authenticated
:class:`~horovod_tpu.runner.network.BasicService` (the KVStoreServer
analogue, ``runner/http/http_server.py``).
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Any, Callable, List, Optional

from horovod_tpu.runner import launch as launch_mod
from horovod_tpu.runner.network import (
    AckResponse,
    BasicClient,
    BasicService,
    make_secret_key,
)


class GetFuncRequest:
    pass


class FuncResponse:
    def __init__(self, payload: bytes):
        self.payload = payload


class ResultRequest:
    def __init__(self, rank: int, payload: bytes):
        self.rank = rank
        self.payload = payload


def run(fn: Callable, args=(), kwargs=None, np: int = 1,
        hosts: Optional[str] = None, verbose: bool = False,
        extra_env: Optional[dict] = None) -> List[Any]:
    """Run ``fn(*args, **kwargs)`` on ``np`` workers; returns the list of
    per-rank return values in rank order.

    On a host with TPU chips the caller must not have touched JAX
    (``jax.devices()``, any array, ``hvd.init()``): a chip belongs to
    one process at a time, so a parent that holds the chips leaves its
    workers to fail or hang when they open them.  The same rule as the
    ``hvdrun`` CLI applies to ``np``: one process drives all local
    chips, and more than one worker on a TPU host is refused before
    any worker starts (``launch.check_one_process_per_tpu_host``)."""
    import cloudpickle

    payload = cloudpickle.dumps((fn, tuple(args), dict(kwargs or {})))
    key = make_secret_key()
    results: dict = {}
    done = threading.Event()

    def handler(req):
        if isinstance(req, GetFuncRequest):
            return FuncResponse(payload)
        if isinstance(req, ResultRequest):
            import pickle

            results[req.rank] = pickle.loads(req.payload)
            if len(results) == np:
                done.set()
            return AckResponse()
        raise ValueError(f"unexpected request {type(req).__name__}")

    service = BasicService("run_service", key, handler, host="127.0.0.1")
    service.start()
    try:
        host_addr = f"127.0.0.1:{service.port}"
        argv = ["-np", str(np)]
        if hosts:
            argv += ["-H", hosts]
        if verbose:
            argv += ["--verbose"]
        argv += ["--", sys.executable, "-m", "horovod_tpu.runner.run_task"]
        os.environ["HOROVOD_RUN_SERVICE_ADDR"] = host_addr
        os.environ["HOROVOD_RUN_SECRET"] = key
        for k, v in (extra_env or {}).items():
            os.environ[k] = v
        try:
            rc = launch_mod.run_commandline(argv)
        finally:
            os.environ.pop("HOROVOD_RUN_SERVICE_ADDR", None)
            os.environ.pop("HOROVOD_RUN_SECRET", None)
        if rc != 0:
            raise RuntimeError(f"horovod_tpu.runner.run failed with exit "
                               f"code {rc}")
        if not done.wait(timeout=30):
            missing = sorted(set(range(np)) - set(results))
            raise RuntimeError(f"no results from ranks {missing}")
        return [results[r] for r in range(np)]
    finally:
        service.shutdown()


def _task_main() -> None:
    """Worker entry (``python -m horovod_tpu.runner.run_task``): fetch the
    function, execute, report the result."""
    import pickle

    import cloudpickle

    addr = os.environ["HOROVOD_RUN_SERVICE_ADDR"]
    key = os.environ["HOROVOD_RUN_SECRET"]
    rank = int(os.environ.get("HOROVOD_RANK", "0"))
    host, port = addr.rsplit(":", 1)
    client = BasicClient((host, int(port)), key)
    fn, args, kwargs = cloudpickle.loads(
        client.request(GetFuncRequest()).payload)
    result = fn(*args, **kwargs)
    client.request(ResultRequest(rank, pickle.dumps(result)))
