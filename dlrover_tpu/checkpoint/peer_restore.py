"""Peer-to-peer elastic restore: the replacement rank's shards come from
surviving hosts' memory, not from Orbax storage.

Why: after a single-host failure the survivors still hold every replicated
shard of the model/optimizer state — restoring the replacement from storage
moves the whole multi-GB state through disk, which is most of an at-scale
restore, while a toy restore takes seconds. ElasWave's in-memory state redistribution and
the Orbax distributed-checkpointing paper (PAPERS.md) are the blueprints.

The pieces, in data-flow order:

- :class:`PeerStateStore` (worker): at every checkpoint boundary the live
  state is mirrored leaf-by-leaf into a host-RAM staging directory the
  agent owns (the same bytes the Orbax save just staged, so peer step N
  and Orbax step N are the SAME consistent cut). The manifest — step,
  per-shard dtype/shape/CRC, the data-position state — is written last,
  atomically, so a SIGKILL mid-stage leaves the previous step intact.
- :class:`PeerDonorServer` (agent): a tiny length-prefixed TCP protocol
  serving staged shards. It lives in the AGENT process, so it survives the
  worker restarts a membership change forces — that is what makes the
  staged bytes "surviving HBM" from the replacement's point of view.
- The master's restore plan (master/rendezvous.py ``compute_restore_plan``)
  maps each staged shard key to a surviving donor, stamped with the
  ``world_epoch`` so a second failure mid-transfer invalidates the plan.
- :class:`PeerRestorer` (worker): plan → parallel shard fetch (local cache
  hits short-circuit the network) → epoch re-validation → device arrays
  via the resharding primitive (parallel/sharding.sharded_from_host).
  Shards no surviving replica holds degrade shard-wise to Orbax at the
  SAME step (``mixed``); anything less consistent falls back wholesale
  (``orbax``) — never a silent zero-init.
"""

from __future__ import annotations

import json
import os
import shutil
import socket
import socketserver
import threading
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from dlrover_tpu import obs
from dlrover_tpu.common.config import Context
from dlrover_tpu.common.constants import DefaultValues, NodeEnv
from dlrover_tpu.common.log import default_logger as logger

MANIFEST = "manifest.json"
# stage dirs retained beyond the current one: a donor restaging a newer
# step must not yank the files a plan computed moments ago points at
_RETAIN_STAGES = 2
_HEADER_LIMIT = 1 << 20


# ---------------------------------------------------------------------------
# shard keys + host copies
# ---------------------------------------------------------------------------


def shard_items(tree: Any) -> List[Tuple[str, Any]]:
    """(key, leaf) pairs in canonical tree order; the key is the leaf's
    path string — identical on the staging and restoring side as long as
    both hold the same state structure (they do: it is the same model)."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in flat]


def host_copy(leaf: Any) -> Optional[np.ndarray]:
    """Device leaf → host ndarray, or None when this process cannot see
    the whole leaf (sharded across hosts with no local replica — exactly
    the shards that die with a host and force the Orbax fallback)."""
    import jax

    if isinstance(leaf, jax.Array):
        if getattr(leaf, "is_fully_replicated", False):
            try:
                return np.asarray(leaf.addressable_data(0))
            except Exception:  # noqa: BLE001 — backend-specific failures
                return None
        if getattr(leaf, "is_fully_addressable", True):
            return np.asarray(leaf)
        return None
    return np.asarray(leaf)


def _atomic_write(path: str, payload: bytes) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def load_manifest(directory: str) -> Optional[Dict[str, Any]]:
    """The staged manifest, or None when absent/torn (a torn stage left
    the previous manifest in place — readers never see half a step)."""
    try:
        with open(os.path.join(directory, MANIFEST)) as f:
            manifest = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(manifest, dict) or "shards" not in manifest:
        return None
    return manifest


def load_stage_manifest(directory: str, step: int
                        ) -> Optional[Dict[str, Any]]:
    """The manifest for one SPECIFIC staged step: the current one when
    it matches, else the per-stage copy inside the retained stage dir —
    a donor restaging a newer step mid-transfer must keep serving the
    step an in-flight plan was computed for (that is what the retention
    window exists for)."""
    manifest = load_manifest(directory)
    if manifest is not None and int(manifest.get("step", -1)) == step:
        return manifest
    return load_manifest(os.path.join(directory, f"stage-{step}"))


def manifest_summary(directory: str
                     ) -> Tuple[int, List[str], int]:
    """(step, shard keys, total bytes) of the staged manifest;
    (-1, [], 0) when nothing usable is staged."""
    manifest = load_manifest(directory)
    if manifest is None:
        return -1, [], 0
    shards = manifest.get("shards", {})
    total = sum(int(s.get("nbytes", 0)) for s in shards.values())
    return int(manifest.get("step", -1)), sorted(shards), total


def read_local_shard(directory: str, manifest: Dict[str, Any],
                     key: str) -> Optional[bytes]:
    """Read + CRC-verify one staged shard; None on any mismatch."""
    meta = manifest.get("shards", {}).get(key)
    if meta is None:
        return None
    try:
        path = os.path.join(directory, manifest.get("dir", ""),
                            meta["file"])
        with open(path, "rb") as f:
            data = f.read()
    except (OSError, KeyError):
        return None
    if (len(data) != int(meta.get("nbytes", -1))
            or (zlib.crc32(data) & 0xFFFFFFFF) != int(meta.get("crc32",
                                                               -1))):
        return None
    return data


# ---------------------------------------------------------------------------
# worker-side staging
# ---------------------------------------------------------------------------


class PeerStateStore:
    """Host-RAM mirror of the live state, staged at checkpoint
    boundaries so the bytes outlive the worker process. Single-writer by
    contract (the step loop); readers (the donor server, a respawned
    worker) only ever see a complete step through the atomic manifest."""

    def __init__(self, directory: str):
        self._dir = directory
        # serializes deferred stage writes (joined before the next
        # stage, on flush, and by readers-in-process via flush)
        self._writer: Optional[threading.Thread] = None
        os.makedirs(directory, exist_ok=True)

    @classmethod
    def from_env(cls) -> Optional["PeerStateStore"]:
        directory = os.environ.get(NodeEnv.PEER_CACHE_DIR, "")
        if not directory or not Context.singleton().peer_restore_enabled:
            return None
        return cls(directory)

    @property
    def directory(self) -> str:
        return self._dir

    def stage(self, step: int, state: Any,
              data_state: Optional[Dict[str, Any]] = None,
              defer_write: bool = False) -> bool:
        """Mirror ``state`` (exact dtypes: the live-precision cut — when
        the checkpoint itself stores exact dtypes a peer restore is
        bitwise identical to the Orbax restore of the same step; with a
        quantized checkpoint the peer copy is strictly HIGHER fidelity
        than the storage path) into the cache.

        The device→host copy always runs on the caller (the arrays may
        be donated away by the next train step); with ``defer_write``
        the file writes + CRCs happen on a background thread so the
        step loop only pays the copy. Returns whether anything was
        staged (dispatched, when deferred); never raises into the step
        loop."""
        try:
            host_items: List[Tuple[str, np.ndarray]] = []
            skipped: List[str] = []
            for key, leaf in shard_items(state):
                arr = host_copy(leaf)
                if arr is None:
                    # no local replica of this shard: it dies with the
                    # host — the restore plan will route it to Orbax
                    skipped.append(key)
                    continue
                host_items.append((key, arr))
            if not host_items:
                return False
            self.flush()   # serialize with a previous deferred write
            if not defer_write:
                return self._write_stage(step, host_items, skipped,
                                         dict(data_state or {}))
            self._writer = threading.Thread(
                target=self._write_stage,
                args=(step, host_items, skipped, dict(data_state or {})),
                daemon=True, name=f"peer-stage-{step}")
            self._writer.start()
            return True
        except Exception:  # noqa: BLE001 — staging is an optimization
            logger.warning("peer-state staging at step %d failed", step,
                           exc_info=True)
            return False

    def flush(self) -> None:
        """Join an in-flight deferred stage write (readers in the same
        process call this before trusting the manifest)."""
        writer = self._writer
        if writer is not None and writer.is_alive():
            writer.join()
        self._writer = None

    def _write_stage(self, step: int, host_items, skipped,
                     data_state: Dict[str, Any]) -> bool:
        stage_name = f"stage-{step}"
        tmp = os.path.join(self._dir, f"{stage_name}.tmp")
        final = os.path.join(self._dir, stage_name)
        try:
            shutil.rmtree(tmp, ignore_errors=True)
            os.makedirs(tmp)
            shards: Dict[str, Dict[str, Any]] = {}
            for i, (key, arr) in enumerate(host_items):
                data = np.ascontiguousarray(arr).tobytes()
                fname = f"leaf-{i}.bin"
                with open(os.path.join(tmp, fname), "wb") as f:
                    f.write(data)
                shards[key] = {
                    "file": fname,
                    "dtype": str(arr.dtype),
                    "shape": list(arr.shape),
                    "nbytes": len(data),
                    "crc32": zlib.crc32(data) & 0xFFFFFFFF,
                }
            manifest = {
                "step": int(step),
                "dir": stage_name,
                "staged_at": time.time(),
                "data_state": data_state,
                "shards": shards,
                "skipped": skipped,
            }
            # the per-stage copy rides INSIDE the dir (atomic with the
            # rename): the donor keeps serving this step after a newer
            # stage overwrites the top-level manifest
            with open(os.path.join(tmp, MANIFEST), "w") as f:
                json.dump(manifest, f)
            shutil.rmtree(final, ignore_errors=True)
            os.replace(tmp, final)
            _atomic_write(os.path.join(self._dir, MANIFEST),
                          json.dumps(manifest).encode())
            self._prune(keep=stage_name)
            return True
        except Exception:  # noqa: BLE001 — staging is an optimization
            logger.warning("peer-state staging at step %d failed", step,
                           exc_info=True)
            shutil.rmtree(tmp, ignore_errors=True)
            return False

    def _prune(self, keep: str) -> None:
        """Drop old stage dirs beyond the retention window (the newest
        few stay so an in-flight transfer keyed on the previous step is
        not yanked mid-read)."""
        try:
            stages = sorted(
                (name for name in os.listdir(self._dir)
                 if name.startswith("stage-")
                 and not name.endswith(".tmp")),
                key=lambda n: int(n.split("-")[1])
                if n.split("-")[1].isdigit() else -1)
        except OSError:
            return
        for name in stages[:-_RETAIN_STAGES]:
            if name != keep:
                shutil.rmtree(os.path.join(self._dir, name),
                              ignore_errors=True)


# ---------------------------------------------------------------------------
# donor-side server (runs in the agent: survives worker restarts)
# ---------------------------------------------------------------------------


class _DonorHandler(socketserver.StreamRequestHandler):
    timeout = 30.0

    def handle(self) -> None:  # one connection, many requests
        while True:
            try:
                line = self.rfile.readline(_HEADER_LIMIT)
            except OSError:
                return
            if not line.strip():
                return
            try:
                request = json.loads(line)
            except json.JSONDecodeError:
                self._reply({"ok": False, "error": "bad request"})
                return
            if not self._serve(request):
                return

    def _reply(self, header: Dict[str, Any],
               payload: bytes = b"") -> bool:
        try:
            self.wfile.write(json.dumps(header).encode() + b"\n")
            if payload:
                self.wfile.write(payload)
            self.wfile.flush()
            return True
        except OSError:
            return False

    def _serve(self, request: Dict[str, Any]) -> bool:
        cache_dir = self.server.cache_dir  # type: ignore[attr-defined]
        op = request.get("op", "")
        if op == "manifest":
            # step-addressed when given (a plan's step survives a donor
            # restaging a newer one), the current stage otherwise
            step = request.get("step")
            manifest = (load_stage_manifest(cache_dir, int(step))
                        if step is not None else load_manifest(cache_dir))
            payload = json.dumps(manifest or {}).encode()
            return self._reply({"ok": manifest is not None,
                                "nbytes": len(payload)}, payload)
        if op != "shard":
            return self._reply({"ok": False, "error": f"bad op {op!r}"})
        key = str(request.get("key", ""))
        step = int(request.get("step", -1))
        manifest = load_stage_manifest(cache_dir, step)
        if manifest is None:
            return self._reply({
                "ok": False, "error": f"step {step} not staged"})
        data = read_local_shard(cache_dir, manifest, key)
        if data is None:
            return self._reply({"ok": False,
                                "error": f"shard {key!r} unavailable"})
        meta = manifest["shards"][key]
        # byte-range serving (the resharding-migration stripe mode,
        # master/rendezvous.py compute_restore_plan(stripe=True)): the
        # receiver reassembles ranges from several donors and verifies
        # the FULL-shard CRC carried in every range header. The whole
        # shard was CRC-verified by read_local_shard above, so a range
        # of it is trustworthy too.
        offset = int(request.get("offset", 0) or 0)
        length = request.get("length")
        if offset or length is not None:
            end = (offset + int(length)) if length is not None \
                else len(data)
            if not (0 <= offset <= end <= len(data)):
                return self._reply({
                    "ok": False,
                    "error": f"bad range [{offset}, {end}) of "
                             f"{len(data)}"})
            data = data[offset:end]
        return self._reply({"ok": True, "nbytes": len(data),
                            "crc32": meta["crc32"],
                            "total_nbytes": meta["nbytes"],
                            "dtype": meta["dtype"],
                            "shape": meta["shape"]}, data)


class _DonorTCPServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True


class PeerDonorServer:
    """Serves the local peer-state cache to replacement ranks. Owned by
    the agent so a worker restart (the thing every membership change
    does) never interrupts an in-flight donation."""

    def __init__(self, cache_dir: str, port: Optional[int] = None):
        self._cache_dir = cache_dir
        self._port = (port if port is not None
                      else Context.singleton().peer_donor_port)
        self._server: Optional[_DonorTCPServer] = None
        self._thread: Optional[threading.Thread] = None
        self.addr = ""

    def start(self) -> str:
        from dlrover_tpu.common.comm import local_ip

        server = _DonorTCPServer(("", self._port), _DonorHandler)
        server.cache_dir = self._cache_dir  # type: ignore[attr-defined]
        self._server = server
        self._thread = threading.Thread(
            target=server.serve_forever, kwargs={"poll_interval": 0.2},
            daemon=True, name="peer-donor")
        self._thread.start()
        self.addr = f"{local_ip()}:{server.server_address[1]}"
        logger.info("peer donor serving %s at %s", self._cache_dir,
                    self.addr)
        return self.addr

    def stop(self) -> None:
        # idempotent under concurrent callers: the agent's run-loop
        # finally and an external shutdown() may both land here — swap
        # the fields out first so only one caller tears each down
        server, self._server = self._server, None
        thread, self._thread = self._thread, None
        if server is not None:
            server.shutdown()
            server.server_close()
        if thread is not None:
            thread.join(timeout=5.0)


# ---------------------------------------------------------------------------
# receiver-side fetch
# ---------------------------------------------------------------------------


class _DonorConnection:
    """One persistent connection to a donor; shard requests ride it
    sequentially (the per-donor fetch thread is the only user)."""

    def __init__(self, addr: str, timeout_s: float):
        host, port = addr.rsplit(":", 1)
        self._timeout_s = timeout_s
        self._sock = socket.create_connection((host, int(port)),
                                              timeout=timeout_s)
        self._file = self._sock.makefile("rb")

    def request(self, payload: Dict[str, Any], deadline: float = 0.0
                ) -> Tuple[Dict[str, Any], bytes]:
        """One request/response. ``deadline`` (unix ts) hard-bounds the
        WHOLE body read — a trickling donor must not extend the restore
        past the transfer budget one recv-window at a time (the Orbax
        fallback is waiting)."""
        self._sock.sendall(json.dumps(payload).encode() + b"\n")
        header = json.loads(self._file.readline(_HEADER_LIMIT))
        nbytes = int(header.get("nbytes", 0))
        if not header.get("ok") or not nbytes:
            return header, b""
        chunks: List[bytes] = []
        read = 0
        while read < nbytes:
            if deadline:
                remaining = deadline - time.time()
                if remaining <= 0:
                    raise OSError(
                        f"peer transfer deadline exceeded mid-shard "
                        f"({read}/{nbytes} bytes)")
                self._sock.settimeout(min(self._timeout_s, remaining))
            chunk = self._file.read(min(1 << 20, nbytes - read))
            if not chunk:
                raise OSError(f"short read ({read}/{nbytes})")
            chunks.append(chunk)
            read += len(chunk)
        return header, b"".join(chunks)

    def close(self) -> None:
        try:
            self._file.close()
            self._sock.close()
        except OSError:
            pass


def fetch_manifest(addr: str, timeout_s: float = 10.0,
                   step: Optional[int] = None
                   ) -> Optional[Dict[str, Any]]:
    """One donor's staged manifest (step + data-position state); with
    ``step``, the manifest of that specific retained stage."""
    request = {"op": "manifest"}
    if step is not None:
        request["step"] = int(step)
    try:
        conn = _DonorConnection(addr, timeout_s)
        try:
            header, payload = conn.request(request)
        finally:
            conn.close()
        if not header.get("ok"):
            return None
        return json.loads(payload)
    except (OSError, ValueError, json.JSONDecodeError):
        return None


def _verify(data: bytes, header: Dict[str, Any],
            expected_nbytes: int) -> bool:
    return (len(data) == expected_nbytes
            and int(header.get("nbytes", -1)) == expected_nbytes
            and (zlib.crc32(data) & 0xFFFFFFFF)
            == int(header.get("crc32", -1)))


def _stripe_ranges(nbytes: int, parts: int) -> List[Tuple[int, int]]:
    """Split ``nbytes`` into ``parts`` contiguous (offset, length)
    ranges — the byte-level "who sends which shard slice" of the
    resharding-migration stripe mode. Deterministic, covers every byte
    exactly once, tolerates parts > nbytes (empty tail ranges are
    dropped)."""
    parts = max(1, min(parts, nbytes)) if nbytes > 0 else 1
    base, extra = divmod(nbytes, parts)
    ranges: List[Tuple[int, int]] = []
    offset = 0
    for i in range(parts):
        length = base + (1 if i < extra else 0)
        if length <= 0:
            continue
        ranges.append((offset, length))
        offset += length
    return ranges


def fetch_shards(
    plan: Dict[str, Any],
    wanted: Dict[str, int],
    local_cache_dir: str = "",
    deadline: float = 0.0,
) -> Tuple[Dict[str, bytes], Dict[str, int], List[str]]:
    """Fetch the wanted shard bytes per the plan.

    ``wanted``: key → expected byte count (from the abstract state, the
    authority on dtype/shape). Local cache hits (a survivor restoring on
    its own host) never touch the network. Returns (key → bytes,
    per-donor byte table — "local" for cache hits, missing keys). A
    failed/timed-out/corrupt shard is simply missing: the caller decides
    between the shard-wise Orbax fallback and a wholesale one.

    Striped entries (plan mode "stripe": ``{"ranks": [...], "addrs":
    [...]}``) split the shard's bytes into contiguous ranges fetched
    from several donors in parallel — the resharding migration's
    transfer primitive. The reassembled shard is verified against the
    FULL-shard CRC every range header carries; any failed range fails
    the whole key (missing, never wrong)."""
    step = int(plan.get("step", -1))
    entries = plan.get("entries", {})
    got: Dict[str, bytes] = {}
    donor_bytes: Dict[str, int] = {}
    # addr -> [(key, offset, length or None=whole)]
    remote: Dict[str, List[Tuple[str, int, Optional[int]]]] = {}
    # striped reassembly state: key -> {offset: bytes}, key -> crc set
    striped_parts: Dict[str, Dict[int, bytes]] = {}
    striped_crcs: Dict[str, set] = {}
    striped_expected: Dict[str, int] = {}   # number of ranges issued
    missing: List[str] = []
    local_manifest = (load_stage_manifest(local_cache_dir, step)
                      if local_cache_dir else None)
    for key, nbytes in wanted.items():
        entry = entries.get(key)
        if local_manifest is not None:
            data = read_local_shard(local_cache_dir, local_manifest, key)
            if data is not None and len(data) == nbytes:
                got[key] = data
                donor_bytes["local"] = (donor_bytes.get("local", 0)
                                        + len(data))
                continue
        if not entry:
            missing.append(key)
            continue
        addrs = entry.get("addrs") or []
        if len(addrs) > 1 and nbytes > 0:
            ranges = _stripe_ranges(nbytes, len(addrs))
            striped_expected[key] = len(ranges)
            striped_parts[key] = {}
            striped_crcs[key] = set()
            for addr, (offset, length) in zip(addrs, ranges):
                remote.setdefault(addr, []).append((key, offset,
                                                    length))
            continue
        addr = entry.get("addr") or (addrs[0] if addrs else "")
        if not addr:
            missing.append(key)
            continue
        remote.setdefault(addr, []).append((key, 0, None))

    # collected under `lock` by the per-donor threads
    lock = threading.Lock()
    failed_keys: set = set()

    def _fetch_from(addr: str) -> Tuple[Dict[str, bytes], List[str]]:
        fetched: Dict[str, bytes] = {}
        failed: List[str] = []
        work = remote[addr]
        conn = None
        done: List[Tuple] = []
        try:
            conn = _DonorConnection(addr, timeout_s=30.0)
            for item in work:
                key, offset, length = item
                if deadline and time.time() > deadline:
                    break
                request = {"op": "shard", "key": key, "step": step}
                if length is not None:
                    request["offset"] = offset
                    request["length"] = length
                try:
                    header, data = conn.request(request,
                                                deadline=deadline)
                except (OSError, ValueError):
                    # connection died mid-stream: re-dial once for the
                    # remaining keys of this donor (unless the budget
                    # itself is what killed it)
                    if deadline and time.time() > deadline:
                        raise
                    conn.close()
                    conn = _DonorConnection(addr, timeout_s=30.0)
                    header, data = conn.request(request,
                                                deadline=deadline)
                done.append(item)
                if length is None:
                    if header.get("ok") and _verify(data, header,
                                                    wanted[key]):
                        fetched[key] = data
                    else:
                        failed.append(key)
                    continue
                # striped range: stash the part; the reassembly (and
                # the full-shard CRC check) happens once every donor
                # thread finished
                if header.get("ok") and len(data) == length:
                    with lock:
                        striped_parts[key][offset] = data
                        striped_crcs[key].add(
                            int(header.get("crc32", -1)))
                else:
                    with lock:
                        failed_keys.add(key)
        except (OSError, ValueError) as e:
            logger.warning("peer fetch from %s failed: %s", addr, e)
        finally:
            if conn is not None:
                conn.close()
        # anything not completed on this donor: whole keys fail here,
        # striped keys fail via failed_keys
        for item in work:
            if item in done:
                continue
            key, offset, length = item
            if length is None:
                if key not in fetched and key not in failed:
                    failed.append(key)
            else:
                with lock:
                    failed_keys.add(key)
        return fetched, failed

    if remote:
        with ThreadPoolExecutor(
                max_workers=min(8, len(remote))) as pool:
            for addr, (fetched, failed) in zip(
                    remote, pool.map(_fetch_from, list(remote))):
                got.update(fetched)
                if fetched:
                    donor_bytes[addr] = sum(len(d)
                                            for d in fetched.values())
                missing.extend(failed)
    # striped reassembly: every range present, the donors' full-shard
    # CRCs agree, and the assembled bytes re-hash to that CRC — a
    # failed/disagreeing stripe makes the key MISSING, never wrong
    for key, parts in striped_parts.items():
        nbytes = wanted[key]
        crcs = striped_crcs.get(key) or set()
        if (key in failed_keys
                or len(parts) != striped_expected.get(key, -1)
                or len(crcs) != 1):
            missing.append(key)
            continue
        assembled = b"".join(parts[off] for off in sorted(parts))
        expected_crc = next(iter(crcs))
        if (len(assembled) != nbytes
                or (zlib.crc32(assembled) & 0xFFFFFFFF) != expected_crc):
            missing.append(key)
            continue
        got[key] = assembled
        for addr, work in remote.items():
            contributed = sum(length or 0 for k, off, length in work
                              if k == key and off in parts)
            if contributed:
                donor_bytes[addr] = (donor_bytes.get(addr, 0)
                                     + contributed)
    return got, donor_bytes, missing


# ---------------------------------------------------------------------------
# the worker-side restore orchestration
# ---------------------------------------------------------------------------


class PeerRestorer:
    """Plan → transfer → validate → assemble, with the shard-wise Orbax
    fallback. One instance per ElasticTrainLoop."""

    def __init__(self, client=None, cache: Optional[PeerStateStore] = None,
                 plan_file: str = ""):
        self._client = client
        self._cache = cache
        self._plan_file = (plan_file
                           or os.environ.get(NodeEnv.RESTORE_PLAN_FILE,
                                             ""))
        # resharding-migration mode (set by the loop when a parallelism
        # re-plan changed the target sharding): RPC plans stripe each
        # shard's byte ranges across every same-step holder
        self.stripe = False

    @classmethod
    def from_env(cls, client=None) -> Optional["PeerRestorer"]:
        if not Context.singleton().peer_restore_enabled:
            return None
        cache = PeerStateStore.from_env()
        plan_file = os.environ.get(NodeEnv.RESTORE_PLAN_FILE, "")
        if client is None and cache is None and not plan_file:
            return None
        return cls(client=client, cache=cache, plan_file=plan_file)

    @property
    def cache(self) -> Optional[PeerStateStore]:
        return self._cache

    # -- plan acquisition ---------------------------------------------------
    def _fetch_plan(self) -> Optional[Dict[str, Any]]:
        """Freshest plan first: the master RPC (recomputed now), then
        the plan shipped in the agent's join result, then — standalone,
        no master — a purely local pseudo-plan over this host's cache."""
        if self._client is not None:
            try:
                # stripe passed only when armed: client wrappers/shims
                # predating the kwarg keep working on the default path
                plan = (self._client.get_restore_plan(stripe=True)
                        if self.stripe
                        else self._client.get_restore_plan())
                if plan:
                    return plan
            except Exception:  # noqa: BLE001 — degrade to the file plan
                logger.warning("restore-plan RPC failed; using the "
                               "join-result plan", exc_info=True)
        if self._plan_file:
            try:
                with open(self._plan_file) as f:
                    plan = json.load(f)
                if isinstance(plan, dict) and plan.get("entries"):
                    return plan
            except (OSError, json.JSONDecodeError):
                pass
        if self._cache is not None:
            step, keys, _ = manifest_summary(self._cache.directory)
            if step >= 0:
                # local-only: epoch -1 disables the staleness check
                # (there is no master to have recomputed membership)
                return {"epoch": -1, "step": step,
                        "entries": {key: {"rank": -1, "addr": ""}
                                    for key in keys}}
        return None

    def _current_epoch(self) -> Optional[int]:
        if self._client is None:
            return None
        try:
            return self._client.get_restore_epoch()
        except Exception:  # noqa: BLE001 — treat as unverifiable
            return None

    # -- the restore --------------------------------------------------------
    def restore(self, abstract_state: Any, checkpointer=None,
                timings: Optional[Dict[str, float]] = None,
                _retry: bool = True
                ) -> Optional[Tuple[Any, Dict[str, Any], int, str]]:
        """Restore from surviving peers. Returns (state, data_state,
        step, source) with source ``"peer"`` or ``"mixed"``; None means
        the caller must take the full Orbax path (no plan, no donors, a
        newer Orbax step, or an unrecoverably stale plan)."""
        timings = timings if timings is not None else {}
        recorder = obs.get_flight_recorder()
        t0 = time.monotonic()
        plan = self._fetch_plan()
        timings["peer_plan_s"] = round(time.monotonic() - t0, 3)
        if not plan or not plan.get("entries"):
            return None
        step = int(plan.get("step", -1))
        if step < 0:
            return None
        latest = None
        if checkpointer is not None:
            try:
                latest = checkpointer.latest_step()
            except Exception:  # noqa: BLE001 — storage may be torn
                latest = None
        if latest is not None and latest > step:
            # storage moved past the staged state (e.g. a final commit
            # landed after the last stage): peers would rewind the job
            logger.warning(
                "peer restore: Orbax step %d is newer than the staged "
                "step %d; taking the storage path", latest, step)
            recorder.record_event("peer_restore_skipped", step=step,
                                  orbax_step=latest, reason="stale-stage")
            return None
        wanted: Dict[str, int] = {}
        abstract_by_key: Dict[str, Any] = {}
        for key, leaf in shard_items(abstract_state):
            abstract_by_key[key] = leaf
            wanted[key] = int(np.prod(leaf.shape)
                              * np.dtype(leaf.dtype).itemsize)
        deadline = time.time() + DefaultValues.PEER_RESTORE_TIMEOUT_S
        t0 = time.monotonic()
        local_dir = self._cache.directory if self._cache else ""
        with obs.span("restore_peer_transfer",
                      {"step": step,
                       "shards": len(wanted)}) as transfer_span:
            got, donor_bytes, failed = fetch_shards(
                plan, wanted, local_cache_dir=local_dir,
                deadline=deadline)
            transfer_s = time.monotonic() - t0
            total_bytes = sum(len(d) for d in got.values())
            transfer_span.set_attr("bytes", total_bytes)
            transfer_span.set_attr("donors", len(donor_bytes))
            if transfer_s > 0:
                transfer_span.set_attr(
                    "bandwidth_mbps",
                    round(total_bytes / (1 << 20) / transfer_s, 2))
        timings["peer_transfer_s"] = round(transfer_s, 3)
        timings["peer_bytes"] = float(total_bytes)
        if transfer_s > 0 and total_bytes > 0:
            timings["peer_bandwidth_mbps"] = round(
                total_bytes / (1 << 20) / transfer_s, 2)
        missing = sorted(set(wanted) - set(got))
        # the staleness guard: a second failure that mutated membership
        # after the plan was computed invalidates it — shards fetched
        # from a donor that is now dead/draining may be about to vanish
        # (or already reflect a world this rank is no longer part of).
        # Checked AFTER the transfer, immediately before commit.
        plan_epoch = int(plan.get("epoch", -1))
        if plan_epoch >= 0:
            current = self._current_epoch()
            if current is not None and current != plan_epoch:
                recorder.record_event(
                    "restore_plan_stale", plan_epoch=plan_epoch,
                    current_epoch=current, step=step)
                obs.get_registry().counter(
                    "dlrover_tpu_restore_plan_stale_total",
                    "Restore plans rejected by the world-epoch "
                    "staleness guard").inc()
                logger.warning(
                    "restore plan stale (epoch %d -> %d): %s", plan_epoch,
                    current, "recomputing" if _retry else "falling back "
                    "to Orbax")
                if _retry:
                    return self.restore(abstract_state, checkpointer,
                                        timings, _retry=False)
                return None
        data_state = self._data_state(plan, step, donor_bytes,
                                      checkpointer)
        if missing:
            return self._finish_mixed(
                abstract_state, abstract_by_key, got, missing, step,
                data_state, checkpointer, donor_bytes, timings)
        state = self._assemble(abstract_state, abstract_by_key, got)
        self._record(step, "peer", donor_bytes, missing=0,
                     total_bytes=total_bytes, transfer_s=transfer_s)
        return state, data_state, step, "peer"

    def _data_state(self, plan: Dict[str, Any], step: int,
                    donor_bytes: Dict[str, int], checkpointer
                    ) -> Dict[str, Any]:
        """The data-position state of the restored step (sampler
        position + the master's shard checkpoint — the same JSON the
        Orbax data item carries). Local manifest first, then any remote
        donor that served us, then the committed Orbax data item; a
        genuinely unrecoverable position is LOUD (flight event +
        warning) — a silently reset sampler would replay seen data."""
        if self._cache is not None:
            manifest = load_stage_manifest(self._cache.directory, step)
            if manifest is not None:
                return dict(manifest.get("data_state", {}))
        for addr in donor_bytes:
            if addr == "local":
                continue
            manifest = fetch_manifest(addr, step=step)
            if manifest is not None and \
                    int(manifest.get("step", -1)) == step:
                return dict(manifest.get("data_state", {}))
        if checkpointer is not None:
            data = checkpointer.restore_data_state(step)
            if data is not None:
                return data
        obs.get_flight_recorder().record_event(
            "peer_restore_no_data_state", step=step)
        logger.warning(
            "peer restore: no data-position state recoverable for step "
            "%d (no donor manifest, step not in storage) — the sampler "
            "position resets", step)
        return {}

    def _assemble(self, abstract_state: Any,
                  abstract_by_key: Dict[str, Any],
                  got: Dict[str, bytes],
                  overlay: Optional[Dict[str, Any]] = None) -> Any:
        """Fetched bytes (+ optional Orbax overlay leaves) → device
        arrays in the abstract state's shardings."""
        import jax

        from dlrover_tpu.parallel.sharding import sharded_from_host

        host_leaves: Dict[str, Any] = {}
        for key, leaf in abstract_by_key.items():
            if key in got:
                # an OWNED, writable, numpy-aligned copy — never a view
                # over the fetched bytes: jax's CPU path zero-copy
                # aliases host buffers, and the train step's donated
                # state update would then write into the (read-only,
                # unaligned) bytes payload — observed as glibc heap
                # corruption a few steps after restore. pop() drops the
                # raw bytes as we go so peak host memory stays ~2x the
                # state, not 3x.
                host_leaves[key] = np.frombuffer(
                    got.pop(key), dtype=leaf.dtype
                ).reshape(leaf.shape).copy()
            else:
                host_leaves[key] = (overlay or {})[key]
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            abstract_state)
        ordered = [host_leaves[jax.tree_util.keystr(path)]
                   for path, _ in flat]
        host_tree = jax.tree_util.tree_unflatten(treedef, ordered)
        return sharded_from_host(host_tree, abstract_state)

    def _finish_mixed(self, abstract_state, abstract_by_key, got,
                      missing, step, data_state, checkpointer,
                      donor_bytes, timings):
        """Shard-wise degradation: the shards no surviving replica holds
        come from Orbax at the SAME step (mixing steps would assemble a
        state that never existed). Loud by design — this is the failure
        domain doing damage, not business as usual."""
        recorder = obs.get_flight_recorder()
        if checkpointer is None or \
                step not in set(checkpointer.all_steps() or ()):
            recorder.record_event(
                "peer_restore_fallback", step=step, source="orbax",
                missing=len(missing), sample=missing[:5],
                reason="staged step not committed to storage")
            logger.error(
                "peer restore: %d shard(s) unavailable from any "
                "surviving peer and step %d is not in storage — "
                "falling back to the full Orbax restore", len(missing),
                step)
            return None
        logger.error(
            "peer restore DEGRADED: no surviving replica for %d "
            "shard(s) (e.g. %s) — reading them from Orbax step %d",
            len(missing), ", ".join(missing[:3]), step)
        recorder.record_event(
            "peer_restore_fallback", step=step, source="mixed",
            missing=len(missing), sample=missing[:5],
            reason="no surviving replica; shard-wise Orbax read")
        t0 = time.monotonic()
        with obs.span("restore_tensor_read",
                      {"step": step, "mixed": True}):
            orbax_state, orbax_data, _ = checkpointer.restore_step(
                step, abstract_state)
        timings["orbax_read_s"] = round(time.monotonic() - t0, 2)
        overlay = {key: leaf
                   for key, leaf in shard_items(orbax_state)
                   if key in missing}
        if not data_state:
            data_state = orbax_data
        transferred = sum(len(d) for d in got.values())
        state = self._assemble(abstract_state, abstract_by_key, got,
                               overlay=overlay)
        self._record(step, "mixed", donor_bytes, missing=len(missing),
                     total_bytes=transferred,
                     transfer_s=timings.get("peer_transfer_s", 0.0))
        return state, data_state, step, "mixed"

    def _record(self, step: int, source: str,
                donor_bytes: Dict[str, int], missing: int,
                total_bytes: int, transfer_s: float) -> None:
        registry = obs.get_registry()
        registry.counter(
            "dlrover_tpu_restore_source_total",
            "Elastic restores by state source",
            labelnames=("source",)).labels(source=source).inc()
        registry.gauge(
            "dlrover_tpu_checkpoint_restore_bytes",
            "Bytes read by the last checkpoint restore",
            labelnames=("source",)).labels(source="peer").set(
            float(total_bytes))
        if transfer_s > 0 and total_bytes > 0:
            registry.gauge(
                "dlrover_tpu_checkpoint_restore_bandwidth_mbps",
                "Effective bandwidth of the last restore's "
                "tensor-transfer phase",
                labelnames=("source",)).labels(source="peer").set(
                round(total_bytes / (1 << 20) / transfer_s, 2))
        obs.get_flight_recorder().record_event(
            "peer_restore", step=step, source=source,
            bytes=total_bytes, missing=missing,
            donors={str(k): v for k, v in donor_bytes.items()})
        logger.info(
            "peer restore at step %d: source=%s %.1f MiB from %d "
            "donor(s) in %.2fs%s", step, source, total_bytes / (1 << 20),
            len(donor_bytes), transfer_s,
            f" ({missing} shard(s) via Orbax)" if missing else "")
