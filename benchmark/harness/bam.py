"""A minimal unaligned-BAM writer and reader of the benchmark's own.

The input file is traffic and the output file is what `correct` reads, so
neither goes through the program's `io/bam.py`.  Only what PacBio subread
and CCS records need: no reference, no CIGAR, tags of types i, f, Z and
B (i, f, and the small integer types a writer may choose).
"""

from __future__ import annotations

import gzip
import hashlib
import struct
import zlib

import numpy as np

from .simulate import MOVIE

BGZF_EOF = bytes.fromhex("1f8b08040000000000ff0600424302001b0003000000000000000000")
_BLOCK = 0xff00
_SCALAR = {"A": ("c", 1), "c": ("b", 1), "C": ("B", 1), "s": ("h", 2),
           "S": ("H", 2), "i": ("i", 4), "I": ("I", 4), "f": ("f", 4)}


def _bgzf(data: bytes, level: int = 1) -> bytes:
    out = bytearray()
    for i in range(0, len(data), _BLOCK):
        raw = data[i:i + _BLOCK]
        c = zlib.compressobj(level, zlib.DEFLATED, -15)
        comp = c.compress(raw) + c.flush()
        out += struct.pack("<BBBBIBBHBBHH", 0x1f, 0x8b, 8, 4, 0, 0, 0xff, 6,
                           0x42, 0x43, 2, len(comp) + 25)
        out += comp + struct.pack("<II", zlib.crc32(raw), len(raw))
    return bytes(out) + BGZF_EOF


def read_group_id(movie: str, read_type: str) -> str:
    return hashlib.md5(f"{movie}//{read_type}".encode()).hexdigest()[:8]


def write_subread_bam(path: str, zmws: list[dict]) -> None:
    """P6-C4 subread BAM (binding kit 100356300, sequencing kit 100356200,
    basecaller 2.3.0) of the given ZMWs, 50 bases of adapter apart."""
    rg = read_group_id(MOVIE, "SUBREAD")
    text = ("@HD\tVN:1.5\tSO:unknown\tpb:3.0b7\n"
            f"@RG\tID:{rg}\tPL:PACBIO\tDS:READTYPE=SUBREAD;BINDINGKIT=100356300;"
            "SEQUENCINGKIT=100356200;BASECALLERVERSION=2.3.0"
            f"\tPU:{MOVIE}\n").encode()
    out = bytearray(b"BAM\x01" + struct.pack("<i", len(text)) + text
                    + struct.pack("<i", 0))
    nibble = np.array([1, 2, 4, 8], np.uint8)       # A C G T
    for z in zmws:
        start = 0
        for read in z["reads"]:
            n = len(read)
            name = f"{MOVIE}/{z['hole']}/{start}_{start + n}".encode() + b"\0"
            codes = nibble[read]
            if n % 2:
                codes = np.append(codes, np.uint8(0))
            packed = ((codes[0::2] << 4) | codes[1::2]).astype(np.uint8).tobytes()
            tags = (b"RGZ" + rg.encode() + b"\0" + b"zmi" + struct.pack("<i", z["hole"])
                    + b"cxi" + struct.pack("<i", 3) + b"rqf" + struct.pack("<f", 0.85)
                    + b"snBf" + struct.pack("<I4f", 4, *z["snr"]))
            body = (struct.pack("<iiBBHHHiiii", -1, -1, len(name), 255, 0, 0, 4,
                                n, -1, -1, 0)
                    + name + packed + b"\xff" * n + tags)
            out += struct.pack("<i", len(body)) + body
            start += n + 50
    with open(path, "wb") as f:
        f.write(_bgzf(bytes(out)))


def _tags(data: bytes) -> dict:
    tags, off = {}, 0
    while off + 3 <= len(data):
        key, typ = data[off:off + 2].decode(), chr(data[off + 2])
        off += 3
        if typ in _SCALAR:
            fmt, size = _SCALAR[typ]
            tags[key] = struct.unpack_from("<" + fmt, data, off)[0]
            off += size
        elif typ == "Z":
            end = data.index(b"\0", off)
            tags[key] = data[off:end].decode()
            off = end + 1
        elif typ == "B":
            fmt, size = _SCALAR[chr(data[off])]
            n = struct.unpack_from("<I", data, off + 1)[0]
            tags[key] = list(struct.unpack_from(f"<{n}{fmt}", data, off + 5))
            off += 5 + n * size
        else:
            raise ValueError(f"tag {key}: type {typ!r} is not read here")
    return tags


def read_bam(path: str) -> list[dict]:
    """Every record of an unaligned BAM: name, seq, qual (phred+33), tags."""
    with open(path, "rb") as f:
        data = gzip.decompress(f.read())
    if data[:4] != b"BAM\x01":
        raise ValueError(f"{path} is not a BAM file")
    off = 8 + struct.unpack_from("<i", data, 4)[0]
    n_ref = struct.unpack_from("<i", data, off)[0]
    off += 4
    for _ in range(n_ref):
        off += 8 + struct.unpack_from("<i", data, off)[0]
    letters = np.frombuffer(b"=ACMGRSVTWYHKDBN", "S1")
    recs = []
    while off < len(data):
        size = struct.unpack_from("<i", data, off)[0]
        body = data[off + 4: off + 4 + size]
        off += 4 + size
        (_r, _p, l_name, _q, _b, n_cigar, _flag, l_seq, _nr, _np,
         _tl) = struct.unpack_from("<iiBBHHHiiii", body)
        at = 32
        name = body[at: at + l_name - 1].decode()
        at += l_name + 4 * n_cigar
        packed = np.frombuffer(body, np.uint8, (l_seq + 1) // 2, at)
        codes = np.stack([packed >> 4, packed & 15], axis=1).ravel()[:l_seq]
        at += (l_seq + 1) // 2
        qual = np.frombuffer(body, np.uint8, l_seq, at)
        at += l_seq
        recs.append({"name": name, "seq": letters[codes].tobytes().decode(),
                     "qual": ("" if l_seq and qual[0] == 0xff
                              else (qual + 33).astype(np.uint8).tobytes().decode()),
                     "tags": _tags(body[at:])})
    return recs
