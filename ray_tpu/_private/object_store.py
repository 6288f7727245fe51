"""Node-local shared-memory object store (Python client).

Capability parity target: the reference's plasma store
(/root/reference/src/ray/object_manager/plasma/store.h:55) — an immutable
shared-memory object store with create/seal/get/delete semantics, one per
node, read zero-copy by every worker process on the node.

Design (TPU-native twist): instead of a single dlmalloc arena served over a
unix socket with fd passing, each object is its own tmpfs-backed segment under
``/dev/shm``. *Seal* is an atomic ``rename(2)`` within the tmpfs: an object is
visible if and only if it has been sealed, so readers never observe partial
writes and no extra sealed-flag protocol is needed. The C++ native store
(``src/store/``) implements capacity accounting, LRU eviction and spilling on
top of the same segment layout, so Python clients work with either backend.

Reads ``mmap`` the segment and hand a ``memoryview`` to the deserializer —
large numpy arrays come out zero-copy.
"""

from __future__ import annotations

import collections
import mmap
import os
import time
from typing import Optional

from .ids import ObjectID

SHM_DIR = os.environ.get("RT_SHM_DIR", "/dev/shm")

# Default capacity of the shm arena before segments spill to disk —
# shared with the native store (src/store/) so both backends enforce
# the same ceiling.
_DEFAULT_CAPACITY = 2 * 1024 ** 3

# Resync the cached used-bytes figure against the filesystem at least
# every N optimistic puts: peer processes (node + every worker share the
# session dir) put segments this instance never sees.
_USED_SYNC_EVERY = 32

# How old an UNSTAMPED session dir must be before the reaper treats it as
# debris (a dir mid-creation has no .owner for a few microseconds).
_ORPHAN_UNSTAMPED_AGE_S = 300.0


def _proc_start_time(pid: int) -> Optional[int]:
    """Kernel start tick of `pid` (field 22 of /proc/<pid>/stat) — pid
    liveness alone is reuse-prone; pid+starttime identifies a process."""
    try:
        with open(f"/proc/{pid}/stat", "rb") as f:
            stat = f.read()
        # comm may contain spaces/parens: fields are after the LAST ')'.
        return int(stat[stat.rindex(b")") + 2:].split()[19])
    except (OSError, ValueError, IndexError):
        return None


def _stamp_owner(prefix: str) -> None:
    """First creator of a session dir records its identity so crashed
    sessions (kill -9 leaves no atexit) can be reaped by the next init.
    Reference: the raylet cleans up leftover plasma/session dirs of dead
    sessions on startup (services.py session cleanup)."""
    path = os.path.join(prefix, ".owner")
    try:
        fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY, 0o644)
    except FileExistsError:
        return  # a peer process of the same session got here first
    except OSError:
        return
    pid = os.getpid()
    with os.fdopen(fd, "w") as f:
        f.write(f"{pid} {_proc_start_time(pid) or 0}")


def _owner_alive(prefix: str) -> Optional[bool]:
    """True/False = owner known alive/dead; None = no stamp."""
    try:
        with open(os.path.join(prefix, ".owner")) as f:
            parts = f.read().split()
        pid, start = int(parts[0]), int(parts[1])
    except (OSError, ValueError, IndexError):
        return None
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True  # someone else's live process
    if start:
        now_start = _proc_start_time(pid)
        if now_start is not None and now_start != start:
            return False  # pid reused by a different process
    return True


def reap_orphan_sessions() -> list[str]:
    """Remove session object-store dirs (and their spill dirs) whose
    owning process is gone — kill -9'd daemons, crashed drivers, chaos
    tests. Swept on every ``init()`` so debris from dead sessions never
    accumulates in /dev/shm (which is RAM!). Returns reaped dir names."""
    import shutil

    def read_spill_sidecar(prefix):
        try:
            with open(os.path.join(prefix, ".spill")) as f:
                return f.read().strip() or None
        except OSError:
            return None

    reaped = []
    try:
        names = os.listdir(SHM_DIR)
    except OSError:
        return reaped
    # Pass 1 — classify sessions and collect every spill path a LIVE
    # session references: a shared custom RT_SPILL_DIR must never be
    # removed out from under a running cluster.
    dead, live_spills = [], set()
    for name in names:
        if not name.startswith("rtpu-"):
            continue
        prefix = os.path.join(SHM_DIR, name)
        if not os.path.isdir(prefix):
            continue
        alive = _owner_alive(prefix)
        if alive is None:
            try:
                age = time.time() - os.stat(prefix).st_mtime
            except OSError:
                continue
            alive = age < _ORPHAN_UNSTAMPED_AGE_S  # mid-creation grace
        spill = read_spill_sidecar(prefix)
        if alive:
            if spill:
                live_spills.add(os.path.realpath(spill))
        else:
            dead.append((name, prefix, spill))
    # Pass 2 — reap dead sessions + their spill dirs (sidecar path when
    # recorded and unshared, plus the default /tmp location).
    for name, prefix, spill in dead:
        shutil.rmtree(prefix, ignore_errors=True)
        session = name[len("rtpu-"):]
        if spill and os.path.realpath(spill) not in live_spills:
            shutil.rmtree(spill, ignore_errors=True)
        shutil.rmtree(os.path.join("/tmp", "rtpu-spill-" + session),
                      ignore_errors=True)
        reaped.append(name)
    # Spill dirs whose session dir is already gone (clean shutdown paths
    # that never reached destroy(), chaos kills): sweep stale ones.
    try:
        spills = os.listdir("/tmp")
    except OSError:
        spills = []
    for name in spills:
        if not name.startswith("rtpu-spill-"):
            continue
        session = name[len("rtpu-spill-"):]
        if os.path.isdir(os.path.join(SHM_DIR, "rtpu-" + session)):
            continue  # session still live (or pending its own reap rules)
        path = os.path.join("/tmp", name)
        try:
            if time.time() - os.stat(path).st_mtime < _ORPHAN_UNSTAMPED_AGE_S:
                continue
        except OSError:
            continue
        shutil.rmtree(path, ignore_errors=True)
    return reaped


class SharedMemoryStore:
    """Client for the per-node segment store.

    All processes on a node construct this with the same ``session_id`` and
    see the same objects.

    Capacity + spill (plasma parity): the arena is bounded by
    ``capacity_bytes`` (RT_STORE_CAPACITY). A put that would exceed it
    moves least-recently-used unpinned sealed segments out to
    ``spill_dir`` (RT_SPILL_DIR, default ``/tmp/rtpu-spill-<session>``);
    ``get``/``wait`` restore spilled segments transparently, so readers
    never observe the spill. The spill dir is recorded in a ``.spill``
    sidecar so the orphan reaper removes it with the session. Every
    spill/restore site calls :meth:`_spill_event`, which appends to a
    shared O_APPEND log — counters in :meth:`stats` are therefore
    coherent across the node + worker processes sharing the session.
    """

    def __init__(self, session_id: str, *, capacity_bytes: int | None = None,
                 spill_dir: str | None = None):
        self.session_id = session_id
        self.prefix = os.path.join(SHM_DIR, f"rtpu-{session_id}")
        os.makedirs(self.prefix, exist_ok=True)
        _stamp_owner(self.prefix)
        if capacity_bytes is None:
            capacity_bytes = int(os.environ.get(
                "RT_STORE_CAPACITY", _DEFAULT_CAPACITY))
        if spill_dir is None:
            spill_dir = os.environ.get(
                "RT_SPILL_DIR", f"/tmp/rtpu-spill-{session_id}")
        self.capacity_bytes = capacity_bytes
        self.spill_dir = spill_dir
        # Record where this session spills so the orphan reaper can
        # remove it even under a custom RT_SPILL_DIR.
        try:
            with open(os.path.join(self.prefix, ".spill"), "w") as f:
                f.write(spill_dir)
        except OSError:
            pass
        # Keep mmaps alive while memoryviews of them circulate.
        self._mmaps: dict[ObjectID, tuple[mmap.mmap, memoryview]] = {}
        # Used-bytes cache: scandir truth + optimistic increments, resynced
        # every _USED_SYNC_EVERY puts (peers put into the same dir).
        self._used_cache = -1  # -1 = never synced
        self._puts_since_sync = 0
        self._log_path = os.path.join(self.prefix, ".spill_log")
        self._log_off = 0
        self._counters = {"created": 0, "evicted": 0, "spilled": 0,
                          "restored": 0, "spilled_bytes": 0,
                          "restored_bytes": 0}
        # Recent spill/restore events for doctor/debug surfaces.
        self.events: collections.deque = collections.deque(maxlen=64)

    def _path(self, oid: ObjectID) -> str:
        return os.path.join(self.prefix, oid.hex())

    def _spill_path(self, oid: ObjectID) -> str:
        return os.path.join(self.spill_dir, oid.hex())

    # -- capacity / spill --------------------------------------------------
    def _spill_event(self, kind: str, oid_hex: str, nbytes: int) -> None:
        """Record one spill/restore event. The O_APPEND write (<< PIPE_BUF,
        so atomic) makes the counters a SESSION-wide ledger: the telemetry
        sampler reads the node instance's stats() and still sees spills
        performed by worker processes."""
        self.events.append((time.time(), kind, oid_hex, nbytes))
        try:
            fd = os.open(self._log_path,
                         os.O_CREAT | os.O_WRONLY | os.O_APPEND, 0o644)
            try:
                os.write(fd, f"{kind} {nbytes}\n".encode())
            finally:
                os.close(fd)
        except OSError:
            pass

    def _read_spill_log(self) -> None:
        """Fold unseen spill-log lines into the counter dict (incremental:
        remembers the byte offset it has consumed)."""
        try:
            with open(self._log_path, "rb") as f:
                f.seek(self._log_off)
                data = f.read()
        except OSError:
            return
        if not data:
            return
        # Only consume whole lines; a peer's write is atomic but may land
        # between our seek and read boundary-aligned anyway.
        end = data.rfind(b"\n") + 1
        if end == 0:
            return
        self._log_off += end
        c = self._counters
        for line in data[:end].splitlines():
            try:
                kind, nbytes = line.split()
                n = int(nbytes)
            except ValueError:
                continue
            if kind == b"S":
                c["spilled"] += 1
                c["spilled_bytes"] += n
            elif kind == b"R":
                c["restored"] += 1
                c["restored_bytes"] += n

    def used_bytes(self) -> int:
        """Bytes of sealed segments resident in shm. Sidecars, pin
        markers, and .tmp.* in-flight files are EXCLUDED: the growing
        .spill_log would otherwise nudge an exact-fit arena "just over"
        capacity and force a full-victim spill on every put (in-flight
        puts are accounted through _ensure_capacity's need parameter)."""
        total = 0
        try:
            with os.scandir(self.prefix) as it:
                for e in it:
                    if "." in e.name:
                        continue
                    try:
                        total += e.stat().st_size
                    except OSError:
                        pass
        except OSError:
            pass
        return total

    def _spill_victims(self):
        """Sealed, unpinned segments oldest-access first (mtime is touched
        on every get, so it doubles as the LRU clock)."""
        victims = []
        try:
            with os.scandir(self.prefix) as it:
                for e in it:
                    if "." in e.name:  # sidecars, .pin markers, .tmp.*
                        continue
                    if os.path.exists(e.path + ".pin"):
                        continue
                    try:
                        st = e.stat()
                    except OSError:
                        continue
                    victims.append((st.st_mtime, e.name, e.path, st.st_size))
        except OSError:
            return []
        victims.sort()
        return victims

    def _spill_one(self, name: str, path: str, size: int) -> bool:
        """Move one sealed segment shm -> spill_dir (copy + atomic rename,
        then unlink the shm copy). Concurrent spills of the same object
        are idempotent; readers racing the unlink fall into the restore
        path on their next get."""
        import shutil

        try:
            os.makedirs(self.spill_dir, exist_ok=True)
        except OSError:
            return False
        dst = os.path.join(self.spill_dir, name)
        tmp = dst + f".tmp.{os.getpid()}"
        try:
            shutil.copyfile(path, tmp)
            os.rename(tmp, dst)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False
        # Drop our own mmap so this process stops pinning the dead pages.
        try:
            self.release(ObjectID(bytes.fromhex(name)))
        except ValueError:
            pass
        try:
            os.unlink(path)
        except FileNotFoundError:
            pass  # a peer spilled or deleted it first
        self._spill_event("S", name, size)
        return True

    def evict(self, num_bytes: int) -> int:
        """Spill LRU unpinned sealed segments until >= num_bytes of shm
        is freed (native-store parity name). Returns bytes freed."""
        freed = 0
        for _mtime, name, path, size in self._spill_victims():
            if freed >= num_bytes:
                break
            if self._spill_one(name, path, size):
                freed += size
        return freed

    def _ensure_capacity(self, need: int) -> None:
        """Make room for `need` incoming bytes, spilling LRU victims when
        the arena would overflow. Soft cap: if every segment is pinned the
        put still proceeds (refusing would deadlock task arg pinning)."""
        if self.capacity_bytes <= 0:
            return
        if (self._used_cache >= 0
                and self._puts_since_sync < _USED_SYNC_EVERY
                and self._used_cache + need <= self.capacity_bytes):
            self._used_cache += need
            self._puts_since_sync += 1
            return
        used = self.used_bytes()
        self._puts_since_sync = 0
        excess = used + need - self.capacity_bytes
        if excess > 0:
            used -= self.evict(excess)
        self._used_cache = max(0, used) + need

    def _restore(self, oid: ObjectID) -> bool:
        """Bring a spilled segment back into shm. True if the segment is
        (now) resident — including when a peer's restore won the race."""
        src = self._spill_path(oid)
        try:
            size = os.stat(src).st_size
        except OSError:
            # Not spilled here: maybe a peer already restored it.
            return os.path.exists(self._path(oid))
        import shutil

        self._ensure_capacity(size)
        tmp = self._path(oid) + f".tmp.{os.getpid()}"
        try:
            shutil.copyfile(src, tmp)
            os.rename(tmp, self._path(oid))
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return os.path.exists(self._path(oid))
        try:
            os.unlink(src)
        except FileNotFoundError:
            pass
        self._spill_event("R", oid.hex(), size)
        return True

    def ensure_resident(self, oid: ObjectID) -> bool:
        """Restore `oid` into shm if it sits in the spill dir, WITHOUT
        mmap-caching it (for callers that open the segment path raw,
        e.g. the bulk-transfer sendfile lane)."""
        if os.path.exists(self._path(oid)):
            return True
        return self._restore(oid)

    # -- writer API --------------------------------------------------------
    def put(self, oid: ObjectID, blob: bytes | bytearray | memoryview) -> int:
        """Create and seal in one step. Returns stored size."""
        self._ensure_capacity(len(blob))
        tmp = self._path(oid) + f".tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.rename(tmp, self._path(oid))  # atomic seal
        self._counters["created"] += 1
        return len(blob)

    def create(self, oid: ObjectID, size: int) -> tuple[memoryview, "_PendingSeal"]:
        """Two-phase create: returns a writable buffer + seal handle."""
        self._ensure_capacity(size)
        tmp = self._path(oid) + f".tmp.{os.getpid()}"
        fd = os.open(tmp, os.O_CREAT | os.O_RDWR | os.O_TRUNC, 0o600)
        os.ftruncate(fd, size)
        mm = mmap.mmap(fd, size)
        os.close(fd)
        self._counters["created"] += 1
        return memoryview(mm), _PendingSeal(self, oid, tmp, mm)

    def put_parts(self, oid: ObjectID, parts) -> int:
        """Vectored put: write serialize_parts output straight to the
        segment — one kernel copy per part, no flatten of the (possibly
        multi-GB) serialized form into an intermediate bytes."""
        self._ensure_capacity(sum(len(p) for p in parts))
        tmp = self._path(oid) + f".tmp.{os.getpid()}"
        total = 0
        try:
            with open(tmp, "wb", buffering=0) as f:
                for p in parts:
                    mv = p if isinstance(p, memoryview) else memoryview(p)
                    off = 0
                    # Unbuffered FileIO.write may write SHORT (Linux caps
                    # one write at ~2GiB): loop on the returned count or
                    # a >2GiB part would silently corrupt the object.
                    while off < len(mv):
                        off += f.write(mv[off:])
                    total += len(mv)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        os.rename(tmp, self._path(oid))  # atomic seal
        self._counters["created"] += 1
        return total

    # -- reader API --------------------------------------------------------
    def get(self, oid: ObjectID) -> Optional[memoryview]:
        """Zero-copy read; None if not present/sealed. Spilled segments
        are restored transparently before the mmap."""
        cached = self._mmaps.get(oid)
        if cached is not None:
            return cached[1]
        path = self._path(oid)
        fd = None
        for _ in range(3):  # miss -> restore -> reopen (racing peers)
            try:
                fd = os.open(path, os.O_RDONLY)
                break
            except FileNotFoundError:
                if not self._restore(oid):
                    return None
        if fd is None:
            return None
        try:
            size = os.fstat(fd).st_size
            mm = mmap.mmap(fd, size, prot=mmap.PROT_READ)
        finally:
            os.close(fd)
        try:
            os.utime(path)  # LRU clock for spill victim selection
        except OSError:
            pass
        mv = memoryview(mm)
        self._mmaps[oid] = (mm, mv)
        return mv

    def contains(self, oid: ObjectID) -> bool:
        return (oid in self._mmaps or os.path.exists(self._path(oid))
                or os.path.exists(self._spill_path(oid)))

    def wait(self, oid: ObjectID, timeout: float | None = None) -> Optional[memoryview]:
        """Poll-wait for an object to appear (fallback path; the runtime
        normally waits on seal notifications instead)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        delay = 0.0005
        while True:
            mv = self.get(oid)
            if mv is not None:
                return mv
            if deadline is not None and time.monotonic() >= deadline:
                return None
            time.sleep(delay)
            delay = min(delay * 2, 0.02)

    def release(self, oid: ObjectID):
        entry = self._mmaps.pop(oid, None)
        if entry is not None:
            mm, mv = entry
            mv.release()
            try:
                mm.close()
            except BufferError:
                pass  # views still circulating; GC will close later

    def delete(self, oid: ObjectID):
        self.release(oid)
        for path in (self._path(oid), self._path(oid) + ".pin",
                     self._spill_path(oid)):
            try:
                os.unlink(path)
            except OSError:
                pass

    def pin(self, oid: ObjectID):
        """Exclude `oid` from spill victim selection. Pin markers are
        plain files so they hold across the node + worker processes
        sharing the arena (the node is the only pinner in practice)."""
        try:
            fd = os.open(self._path(oid) + ".pin",
                         os.O_CREAT | os.O_WRONLY, 0o644)
            os.close(fd)
        except OSError:
            pass

    def unpin(self, oid: ObjectID):
        try:
            os.unlink(self._path(oid) + ".pin")
        except OSError:
            pass

    def size_of(self, oid: ObjectID) -> Optional[int]:
        for path in (self._path(oid), self._spill_path(oid)):
            try:
                return os.stat(path).st_size
            except OSError:
                continue
        return None

    def total_bytes(self) -> int:
        total = 0
        with os.scandir(self.prefix) as it:
            for e in it:
                try:
                    total += e.stat().st_size
                except FileNotFoundError:
                    pass
        return total

    def stats(self) -> dict:
        """Session-wide lifecycle counters. created is instance-local (a
        cheap in-process count); spill/restore figures fold in the shared
        .spill_log, so any instance sees events from every process."""
        self._read_spill_log()
        return dict(self._counters)

    def destroy(self):
        """Remove the whole session directory (cluster shutdown)."""
        for oid in list(self._mmaps):
            self.release(oid)
        import shutil

        shutil.rmtree(self.prefix, ignore_errors=True)
        shutil.rmtree(self.spill_dir, ignore_errors=True)


class _PendingSeal:
    def __init__(self, store: SharedMemoryStore, oid: ObjectID, tmp: str, mm: mmap.mmap):
        self._store, self._oid, self._tmp, self._mm = store, oid, tmp, mm

    def seal(self):
        self._mm.flush()
        self._mm.close()
        os.rename(self._tmp, self._store._path(self._oid))

    def abort(self):
        self._mm.close()
        try:
            os.unlink(self._tmp)
        except FileNotFoundError:
            pass


class NativeObjectStore(SharedMemoryStore):
    """The C++-backed store (ray_tpu/_native/cc/store.cc): same segment
    layout and client API as SharedMemoryStore, plus capacity accounting,
    LRU eviction, disk spilling with transparent restore, and
    cross-process pinning. The default store (see make_store)."""

    def __init__(self, session_id: str, *, capacity_bytes: int | None = None,
                 spill_dir: str | None = None):
        # Base init resolves capacity/spill_dir from RT_STORE_CAPACITY /
        # RT_SPILL_DIR and writes the .spill sidecar for the reaper.
        super().__init__(session_id, capacity_bytes=capacity_bytes,
                         spill_dir=spill_dir)
        import ctypes

        from .._native import store_lib

        self._lib = store_lib()
        self._ctypes = ctypes
        self._h = self._lib.rt_store_open(
            self.prefix.encode(), self.capacity_bytes,
            self.spill_dir.encode())

    # -- writer API ---------------------------------------------------------
    def put(self, oid: ObjectID, blob) -> int:
        b = bytes(blob) if not isinstance(blob, bytes) else blob
        if self._lib.rt_store_put(self._h, oid.hex().encode(), b,
                                  len(b)) != 0:
            from .exceptions import OutOfMemoryError

            raise OutOfMemoryError(
                f"object ({len(b)} bytes) exceeds store capacity "
                f"({self.capacity_bytes} bytes) even after eviction")
        return len(b)

    def create(self, oid: ObjectID, size: int):
        fd = self._lib.rt_store_create(self._h, oid.hex().encode(), size)
        if fd < 0:
            from .exceptions import OutOfMemoryError

            raise OutOfMemoryError(
                f"cannot reserve {size} bytes in store "
                f"(capacity {self.capacity_bytes})")
        mm = mmap.mmap(fd, size)
        os.close(fd)
        return memoryview(mm), _NativePendingSeal(self, oid, mm)

    def put_parts(self, oid: ObjectID, parts) -> int:
        """Vectored put into a reserved native segment: capacity-checked
        create, then DIRECT fd writes (one kernel copy per part; no
        mmap setup or msync page walk), then seal."""
        total = sum(len(p) for p in parts)
        fd = self._lib.rt_store_create(self._h, oid.hex().encode(), total)
        if fd < 0:
            from .exceptions import OutOfMemoryError

            raise OutOfMemoryError(
                f"cannot reserve {total} bytes in store "
                f"(capacity {self.capacity_bytes})")
        ok = False
        try:
            for p in parts:
                mv = p if isinstance(p, memoryview) else memoryview(p)
                off = 0
                while off < len(mv):
                    off += os.write(fd, mv[off:])
            ok = True
        finally:
            os.close(fd)
            if not ok:
                # Release the reserved tmp segment (capacity + bytes) —
                # a failed multi-GB put must not ratchet capacity down.
                self._lib.rt_store_abort(self._h, oid.hex().encode())
        if self._lib.rt_store_seal(self._h, oid.hex().encode()) != 0:
            raise OSError(f"seal failed for {oid.hex()}")
        return total

    # -- reader API ---------------------------------------------------------
    def get(self, oid: ObjectID) -> Optional[memoryview]:
        cached = self._mmaps.get(oid)
        if cached is not None:
            return cached[1]
        size = self._ctypes.c_uint64()
        fd = self._lib.rt_store_get(self._h, oid.hex().encode(),
                                    self._ctypes.byref(size))
        if fd < 0:
            return None
        try:
            mm = mmap.mmap(fd, size.value, prot=mmap.PROT_READ)
        finally:
            os.close(fd)
        mv = memoryview(mm)
        self._mmaps[oid] = (mm, mv)
        return mv

    def contains(self, oid: ObjectID) -> bool:
        return oid in self._mmaps or \
            self._lib.rt_store_contains(self._h, oid.hex().encode()) != 0

    def delete(self, oid: ObjectID):
        self.release(oid)
        self._lib.rt_store_delete(self._h, oid.hex().encode())

    # -- native extensions --------------------------------------------------
    def pin(self, oid: ObjectID):
        self._lib.rt_store_pin(self._h, oid.hex().encode())

    def unpin(self, oid: ObjectID):
        self._lib.rt_store_unpin(self._h, oid.hex().encode())

    def used_bytes(self) -> int:
        return self._lib.rt_store_used_bytes(self._h)

    def evict(self, num_bytes: int) -> int:
        return self._lib.rt_store_evict(self._h, num_bytes)

    def stats(self) -> dict:
        c = self._ctypes
        created, evicted, spilled, restored = (c.c_uint64() for _ in range(4))
        self._lib.rt_store_stats(self._h, c.byref(created), c.byref(evicted),
                                 c.byref(spilled), c.byref(restored))
        # The C API reports event counts only; approximate spilled bytes
        # by the spill dir's current disk footprint so the telemetry
        # series is populated on both backends.
        on_disk = 0
        try:
            with os.scandir(self.spill_dir) as it:
                for e in it:
                    try:
                        on_disk += e.stat().st_size
                    except OSError:
                        pass
        except OSError:
            pass
        return {"created": created.value, "evicted": evicted.value,
                "spilled": spilled.value, "restored": restored.value,
                "spilled_bytes": on_disk, "restored_bytes": 0}

    def destroy(self):
        super().destroy()
        if self._h:
            self._lib.rt_store_close(self._h)
            self._h = None


class _NativePendingSeal:
    def __init__(self, store: NativeObjectStore, oid: ObjectID, mm: mmap.mmap):
        self._store, self._oid, self._mm = store, oid, mm

    def seal(self):
        self._mm.flush()
        self._mm.close()
        if self._store._lib.rt_store_seal(
                self._store._h, self._oid.hex().encode()) != 0:
            raise OSError(f"seal failed for {self._oid.hex()}")

    def abort(self):
        self._mm.close()
        self._store._lib.rt_store_abort(
            self._store._h, self._oid.hex().encode())


def make_store(session_id: str) -> SharedMemoryStore:
    """The node's object store: the native (C++) one, built on first use
    (a build failure raises); the pure-Python store only when
    RT_NATIVE_STORE=0 asks for it."""
    if os.environ.get("RT_NATIVE_STORE", "1") != "0":
        return NativeObjectStore(session_id)
    return SharedMemoryStore(session_id)
