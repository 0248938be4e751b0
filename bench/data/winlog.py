"""Windows system-log records and their predicate pool (CIAO §VII-B).

The paper's WinLog is Loghub's Windows CBS log (114,608,388 lines in
26.09 GB, about 228 bytes a line; https://github.com/logpai/loghub).
The schema, key order and the distributions of time, level and service
are those of the program's ``data/datasets.py:winlog_record``; each
message starts as one of its templates does and runs on as the CBS
lines do (component paths, package names, HRESULTs), so that a record's
text is about as long as a Loghub line: about 285 bytes of JSON a
record.  Drawn column by column.  The pool is Table II's WinLog row, as
``predicate_pool("winlog")`` builds it: substring clauses only, on the
message text and on the time stamp.
"""
from __future__ import annotations

import numpy as np

from .render import Table, cat, digits, vocab

SERVICES = (
    "CBS TrustedInstaller WindowsUpdateAgent SessionManager NetworkProfile "
    "Defender Scheduler DHCP DNSCache EventLog"
).split()
TEMPLATES = (
    "Loaded Servicing Stack v6.1.7601.{n} with Core: C:\\Windows\\winsxs\\"
    "amd64_microsoft-windows-servicingstack_31bf3856ad364e35_6.1.7601."
    "23505_none_681aa442f6fed7f0\\cbscore.dll, version 6.1.7601.23505, "
    "client id WindowsUpdateAgent",
    "Warning: Unrecognized packageExtended attribute {n} in package "
    "Package_for_KB2919355~31bf3856ad364e35~amd64~~6.3.1.14, "
    "C:\\Windows\\servicing\\Packages\\Package_for_KB2919355~"
    "31bf3856ad364e35~amd64~~6.3.1.14.mum",
    "Failed to connect to endpoint {n} retrying [HRESULT = 0x80070005 - "
    "E_ACCESSDENIED] while querying C:\\Windows\\SoftwareDistribution"
    "\\DataStore\\DataStore.edb after 3 attempts, next retry in 3600 "
    "seconds",
    "Read out cached package applicability for package {n}: "
    "Package_for_KB2966583~31bf3856ad364e35~amd64~~6.1.1.1, "
    "ApplicableState: 112, CurrentState:112, Source: "
    "C:\\Windows\\SoftwareDistribution\\Download",
    "Session {n} initialized by client WindowsUpdateAgent, external staging "
    "directory: (null), external registry directory: (null) "
    "[HRESULT = 0x00000000 - S_OK], client version 7.6.7601.23453",
    "Expecting attribute name {n} in manifest "
    "C:\\Windows\\winsxs\\Manifests\\amd64_microsoft-windows-"
    "cbs_31bf3856ad364e35.manifest [HRESULT = 0x800f080d - "
    "CBS_E_MANIFEST_INVALID_ITEM], line 12, column 44",
    "Service {n} entered the running state, startup type: Manual, image "
    "path C:\\Windows\\servicing\\TrustedInstaller.exe, "
    "process id 4832, session 0, account LocalSystem, error control: Normal",
    "Scavenging cache entry {n} complete: removed 31 superseded components "
    "from C:\\Windows\\winsxs\\Temp\\PendingDeletes, "
    "freed 104857600 bytes in 2 passes, next scavenge after 7 days",
)
LEVELS = ["Info", "Warning", "Error"]
FRAGMENTS = (
    "Servicing Stack", "Unrecognized", "Failed to connect", "cached package",
    "initialized by client", "attribute name", "running state", "Scavenging",
)


def generate(n: int, seed: int) -> Table:
    rng = np.random.default_rng(seed)
    num = lambda lo, hi, w: digits(rng.integers(lo, hi, n), w)
    tpl = rng.integers(0, len(TEMPLATES), n)
    head = vocab([t.split("{n}")[0] for t in TEMPLATES], tpl)
    tail = vocab([t.split("{n}")[1] for t in TEMPLATES], tpl)
    return Table({
        "time": cat(b"2016-", num(1, 13, 2), b"-", num(1, 29, 2), b" ",
                    num(0, 24, 2), b":", num(0, 60, 2), b":", num(0, 60, 2),
                    b",", num(0, 1000, 3)),
        "level": vocab(LEVELS, rng.choice(3, size=n, p=[0.8, 0.15, 0.05])),
        "service": vocab(SERVICES, rng.integers(0, len(SERVICES), n)),
        "info": cat(head, num(0, 100000, 0), tail),
    })


def pool() -> list[tuple]:
    """Table II's WinLog predicates as ``(kind, key, value)`` clauses."""
    out: list[tuple] = []
    for i in range(200):
        f = FRAGMENTS[i % len(FRAGMENTS)]
        out.append(("substring", "info",
                    f if i < len(FRAGMENTS) else f"{f} {i}"))
    out += [("substring", "time", f"-{m:02d}-") for m in range(1, 13)]
    out += [("substring", "time", f"-{d:02d} ") for d in range(1, 29)]
    out += [("substring", "time", f" {h:02d}:") for h in range(24)]
    out += [("substring", "time", f":{m:02d}:") for m in range(60)]
    out += [("substring", "time", f":{s:02d},") for s in range(60)]
    return out
