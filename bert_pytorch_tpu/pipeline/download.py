"""Corpus / weights downloaders with integrity verification.

Reference utils/download.py: Wikipedia dump, BooksCorpus, SQuAD, GLUE, and
Google pretrained-weights downloaders with SHA256 verification of the weight
archives (:11-256). Re-expressed as one registry of datasets; checksums are
verified when known. BooksCorpus is a URL-list-driven fetch (the reference
cloned soskek/bookcorpus and ran its downloader over url_list.jsonl,
utils/download.py:59-78 — here the list-driven fetch is in-framework, no git
clone / subprocess). GLUE resolves per-task archives directly (the reference
fetched and exec'd the W4ngatang gist, :81-100). (This build environment has
no egress — downloads are exercised in tests via file:// URLs and checksum
checks on local files.)
"""

from __future__ import annotations

import argparse
import bz2
import hashlib
import json
import os
import shutil
import urllib.request
import zipfile
from dataclasses import dataclass
from typing import Dict, Iterable, Optional


@dataclass
class Resource:
    url: str
    filename: str
    sha256: Optional[str] = None
    extract: bool = False  # zip/bz2 archives


DATASETS: Dict[str, Dict[str, Resource]] = {
    "squad": {
        "train-v1.1.json": Resource(
            "https://rajpurkar.github.io/SQuAD-explorer/dataset/train-v1.1.json",
            "train-v1.1.json"),
        "dev-v1.1.json": Resource(
            "https://rajpurkar.github.io/SQuAD-explorer/dataset/dev-v1.1.json",
            "dev-v1.1.json"),
        "train-v2.0.json": Resource(
            "https://rajpurkar.github.io/SQuAD-explorer/dataset/train-v2.0.json",
            "train-v2.0.json"),
        "dev-v2.0.json": Resource(
            "https://rajpurkar.github.io/SQuAD-explorer/dataset/dev-v2.0.json",
            "dev-v2.0.json"),
    },
    "wikicorpus": {
        "enwiki": Resource(
            "https://dumps.wikimedia.org/enwiki/latest/"
            "enwiki-latest-pages-articles.xml.bz2",
            "enwiki-latest-pages-articles.xml.bz2", extract=True),
    },
    # GLUE per-task archives (the canonical hosting W4ngatang's GLUE
    # download script resolves; reference defaulted to
    # tasks=['MRPC', 'SST'], utils/download.py:81-83).
    "glue": {
        "CoLA": Resource(
            "https://dl.fbaipublicfiles.com/glue/data/CoLA.zip",
            "CoLA.zip", extract=True),
        "SST": Resource(
            "https://dl.fbaipublicfiles.com/glue/data/SST-2.zip",
            "SST-2.zip", extract=True),
        "QQP": Resource(
            "https://dl.fbaipublicfiles.com/glue/data/QQP-clean.zip",
            "QQP.zip", extract=True),
        "STS": Resource(
            "https://dl.fbaipublicfiles.com/glue/data/STS-B.zip",
            "STS-B.zip", extract=True),
        "MNLI": Resource(
            "https://dl.fbaipublicfiles.com/glue/data/MNLI.zip",
            "MNLI.zip", extract=True),
        "QNLI": Resource(
            "https://dl.fbaipublicfiles.com/glue/data/QNLIv2.zip",
            "QNLI.zip", extract=True),
        "RTE": Resource(
            "https://dl.fbaipublicfiles.com/glue/data/RTE.zip",
            "RTE.zip", extract=True),
        "WNLI": Resource(
            "https://dl.fbaipublicfiles.com/glue/data/WNLI.zip",
            "WNLI.zip", extract=True),
        # MRPC ships as two raw txt files, not a zip
        "MRPC-train": Resource(
            "https://dl.fbaipublicfiles.com/senteval/senteval_data/"
            "msr_paraphrase_train.txt", "MRPC/msr_paraphrase_train.txt"),
        "MRPC-test": Resource(
            "https://dl.fbaipublicfiles.com/senteval/senteval_data/"
            "msr_paraphrase_test.txt", "MRPC/msr_paraphrase_test.txt"),
    },
    "google_pretrained_weights": {
        "uncased_L-24_H-1024_A-16": Resource(
            "https://storage.googleapis.com/bert_models/2018_10_18/"
            "uncased_L-24_H-1024_A-16.zip",
            "uncased_L-24_H-1024_A-16.zip", extract=True),
        "uncased_L-12_H-768_A-12": Resource(
            "https://storage.googleapis.com/bert_models/2018_10_18/"
            "uncased_L-12_H-768_A-12.zip",
            "uncased_L-12_H-768_A-12.zip", extract=True),
        "cased_L-24_H-1024_A-16": Resource(
            "https://storage.googleapis.com/bert_models/2018_10_18/"
            "cased_L-24_H-1024_A-16.zip",
            "cased_L-24_H-1024_A-16.zip", extract=True),
    },
}


def sha256_file(path: str, chunk: int = 1 << 20) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            block = f.read(chunk)
            if not block:
                break
            h.update(block)
    return h.hexdigest()


def verify(path: str, expected_sha256: Optional[str]) -> bool:
    """True when the checksum matches (or none is pinned). The reference
    verified the Google weight archives the same way (utils/download.py:
    177-216)."""
    if expected_sha256 is None:
        return True
    return sha256_file(path) == expected_sha256


def fetch(resource: Resource, output_dir: str, force: bool = False) -> str:
    target = os.path.join(output_dir, resource.filename)
    os.makedirs(os.path.dirname(target) or ".", exist_ok=True)
    if os.path.exists(target) and not force \
            and verify(target, resource.sha256):
        print(f"[download] cached: {target}")
        return target

    print(f"[download] {resource.url} -> {target}")
    with urllib.request.urlopen(resource.url) as r, open(target, "wb") as f:
        shutil.copyfileobj(r, f)
    if not verify(target, resource.sha256):
        os.remove(target)
        raise IOError(f"checksum mismatch for {resource.url}")

    if resource.extract:
        extract(target, output_dir)
    return target


def extract(path: str, output_dir: str) -> None:
    if path.endswith(".zip"):
        with zipfile.ZipFile(path) as z:
            z.extractall(output_dir)
    elif path.endswith(".bz2"):
        out = path[:-len(".bz2")]
        with bz2.open(path, "rb") as src, open(out, "wb") as dst:
            shutil.copyfileobj(src, dst)


def iter_url_list(url_list_path: str) -> Iterable[str]:
    """Yield book URLs from a soskek-style url_list.jsonl (each line a JSON
    object whose 'txt' — falling back to 'url' — field is the plain-text
    download) or from a plain newline-delimited URL file."""
    with open(url_list_path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            if line.startswith("{"):
                rec = json.loads(line)
                url = rec.get("txt") or rec.get("url")
                if url:
                    yield url
            else:
                yield line


def fetch_bookscorpus(url_list_path: str, output_dir: str,
                      min_bytes: int = 1024) -> int:
    """Download every book in the URL list into output_dir/bookscorpus.

    In-framework replacement for the reference's cloned downloader
    (utils/download.py:59-78): per-book fetch, undersized/failed files
    dropped (the reference passed --trash-bad-count for the same hygiene).
    Returns the number of books kept."""
    out = os.path.join(output_dir, "bookscorpus")
    os.makedirs(out, exist_ok=True)
    kept = 0
    for i, url in enumerate(iter_url_list(url_list_path)):
        # index prefix disambiguates distinct books whose URLs share a
        # basename (e.g. many .../download.txt links)
        base = os.path.basename(url.rstrip("/")) or "book.txt"
        name = f"{i:06d}_{base}"
        if not name.endswith(".txt"):
            name += ".txt"
        target = os.path.join(out, name)
        if os.path.exists(target) and os.path.getsize(target) >= min_bytes:
            kept += 1
            continue
        try:
            with urllib.request.urlopen(url) as r, open(target, "wb") as f:
                shutil.copyfileobj(r, f)
        except Exception as e:  # noqa: BLE001 — per-book failures are expected
            print(f"[bookscorpus] failed {url}: {e}")
            if os.path.exists(target):
                os.remove(target)
            continue
        if os.path.getsize(target) < min_bytes:
            print(f"[bookscorpus] trashing undersized {name}")
            os.remove(target)
            continue
        kept += 1
    print(f"[bookscorpus] {kept} books kept under {out}")
    return kept


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--dataset", required=True,
                   choices=sorted(DATASETS) + ["bookscorpus"])
    p.add_argument("--output_dir", required=True)
    p.add_argument("--only", default=None,
                   help="fetch a single named resource from the dataset")
    p.add_argument("--url_list", default=None,
                   help="bookscorpus: url_list.jsonl (or plain URL list)")
    p.add_argument("--force", action="store_true")
    args = p.parse_args(argv)

    if args.dataset == "bookscorpus":
        if not args.url_list:
            raise SystemExit("--dataset bookscorpus requires --url_list")
        fetch_bookscorpus(args.url_list, args.output_dir)
        return

    resources = DATASETS[args.dataset]
    if args.only:
        resources = {args.only: resources[args.only]}
    out = os.path.join(args.output_dir, args.dataset)
    for name, res in resources.items():
        fetch(res, out, force=args.force)


if __name__ == "__main__":
    main()
