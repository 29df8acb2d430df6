"""Validate/convert a dataset drop into the layout the parity gates
expect (the activation contract of tests/test_real_data.py).

One command turns "I have the files somewhere" into "the gates run":

    python tools/prepare_data.py --check  /data       # validate only
    python tools/prepare_data.py /downloads /data      # convert + layout

Expected layout under the target MX_DATA_DIR (documented in
tests/test_real_data.py):

  mnist/train-images-idx3-ubyte(.gz)   + train-labels / t10k images+labels
  ptb/ptb.train.txt + ptb.valid.txt
  voc/VOC2007/Annotations/*.xml                 (SSD config 4)
  voc/VOC2007/JPEGImages/*.jpg
  voc/VOC2007/ImageSets/Main/trainval.txt + test.txt
  imagenet/train.rec (+ train.idx)              (optional)

Conversions performed (source dir searched recursively):
  - idx/ptb/voc files found anywhere are hard-linked/copied into place;
  - a directory of class-subdirectory images is packed into train.rec
    via tools/im2rec.py (the reference's im2rec flow);
  - .gz idx files are accepted as-is (the readers decompress).
"""
import argparse
import glob
import gzip
import os
import shutil
import struct
import sys

MNIST_FILES = ("train-images-idx3-ubyte", "train-labels-idx1-ubyte",
               "t10k-images-idx3-ubyte", "t10k-labels-idx1-ubyte")
PTB_FILES = ("ptb.train.txt", "ptb.valid.txt")


def _find(root, name):
    hits = glob.glob(os.path.join(root, "**", name), recursive=True) + \
        glob.glob(os.path.join(root, "**", name + ".gz"), recursive=True)
    return hits[0] if hits else None


def _place(src, dst):
    os.makedirs(os.path.dirname(dst), exist_ok=True)
    if os.path.abspath(src) == os.path.abspath(dst):
        return
    try:
        os.link(src, dst)
    except OSError:
        shutil.copy2(src, dst)


def _check_idx_magic(path, want_dims):
    op = gzip.open if path.endswith(".gz") else open
    with op(path, "rb") as f:
        magic = struct.unpack(">I", f.read(4))[0]
    dims = magic & 0xFF
    if dims != want_dims:
        return "bad idx magic in %s: %d dims, want %d" % (path, dims,
                                                          want_dims)
    return None


def check(target):
    """Validate the layout; returns a list of problems (empty = ready)."""
    problems = []
    mnist_ok = True
    for name in MNIST_FILES:
        p = os.path.join(target, "mnist", name)
        hit = p if os.path.exists(p) else (
            p + ".gz" if os.path.exists(p + ".gz") else None)
        if hit is None:
            problems.append("mnist: missing %s(.gz)" % name)
            mnist_ok = False
        else:
            err = _check_idx_magic(hit, 3 if "images" in name else 1)
            if err:
                problems.append(err)
                mnist_ok = False
    if mnist_ok:
        print("mnist: OK (config 0 accuracy gate will run)")
    ptb_ok = True
    for name in PTB_FILES:
        p = os.path.join(target, "ptb", name)
        if not os.path.exists(p):
            problems.append("ptb: missing %s" % name)
            ptb_ok = False
        elif os.path.getsize(p) < 1000:
            problems.append("ptb: %s is suspiciously small" % name)
            ptb_ok = False
    if ptb_ok:
        print("ptb: OK (config 3 perplexity gate will run)")
    voc = os.path.join(target, "voc", "VOC2007")
    if os.path.isdir(voc):
        voc_ok = True
        for sub in ("Annotations", "JPEGImages"):
            d = os.path.join(voc, sub)
            if not os.path.isdir(d) or not os.listdir(d):
                problems.append("voc: %s/ empty or missing" % sub)
                voc_ok = False
        for split in ("trainval.txt", "test.txt"):
            if not os.path.exists(os.path.join(voc, "ImageSets", "Main",
                                               split)):
                problems.append("voc: ImageSets/Main/%s missing" % split)
                voc_ok = False
        if voc_ok:
            n = len(os.listdir(os.path.join(voc, "JPEGImages")))
            print("voc: OK, %d images (config 4 SSD mAP gate will run)"
                  % n)
    else:
        print("voc: absent (config 4 SSD gate stays skipped)")
    rec = os.path.join(target, "imagenet", "train.rec")
    if os.path.exists(rec):
        print("imagenet: train.rec present (%d MB)"
              % (os.path.getsize(rec) >> 20))
    else:
        print("imagenet: absent")
    return problems


def convert(source, target):
    """Pull recognizable files out of `source` into the target layout."""
    for name in MNIST_FILES:
        hit = _find(source, name)
        if hit:
            base = os.path.basename(hit)
            _place(hit, os.path.join(target, "mnist", base))
    for name in PTB_FILES:
        hit = _find(source, name)
        if hit:
            _place(hit, os.path.join(target, "ptb", name))
    # VOC: find an Annotations dir with its VOC2007 parent structure
    for anns in glob.glob(os.path.join(source, "**", "Annotations"),
                          recursive=True):
        vocroot = os.path.dirname(anns)
        for sub in ("Annotations", "JPEGImages", "ImageSets"):
            s = os.path.join(vocroot, sub)
            if os.path.isdir(s):
                d = os.path.join(target, "voc", "VOC2007", sub)
                if not os.path.isdir(d):
                    shutil.copytree(s, d)
        break
    # class-subdirectory image tree -> train.rec via im2rec
    rec_dst = os.path.join(target, "imagenet", "train.rec")
    if not os.path.exists(rec_dst):
        for cand in sorted(glob.glob(os.path.join(source, "*"))):
            if not os.path.isdir(cand):
                continue
            subdirs = [d for d in sorted(glob.glob(os.path.join(cand, "*")))
                       if os.path.isdir(d)]
            have_imgs = subdirs and any(
                glob.glob(os.path.join(subdirs[0], "*.jpg")) +
                glob.glob(os.path.join(subdirs[0], "*.jpeg")) +
                glob.glob(os.path.join(subdirs[0], "*.png")))
            if not have_imgs:
                continue
            os.makedirs(os.path.dirname(rec_dst), exist_ok=True)
            prefix = rec_dst[:-len(".rec")]
            import subprocess
            print("packing %s -> %s via im2rec" % (cand, rec_dst))
            subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "im2rec.py"),
                 prefix, cand, "--quality", "90"],
                check=True)
            break


def main():
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("source", nargs="?",
                    help="directory to scan for raw downloads "
                         "(omit with --check)")
    ap.add_argument("target", nargs="?",
                    help="MX_DATA_DIR layout root to create/validate")
    ap.add_argument("--check", metavar="DIR",
                    help="validate an existing layout and exit")
    args = ap.parse_args()
    if args.check:
        problems = check(args.check)
        for p in problems:
            print("PROBLEM:", p)
        print("\nactivation: MX_DATA_DIR=%s python -m pytest "
              "tests/test_real_data.py" % args.check)
        return 1 if problems else 0
    if not (args.source and args.target):
        ap.error("need SOURCE TARGET (or --check DIR)")
    convert(args.source, args.target)
    problems = check(args.target)
    for p in problems:
        print("PROBLEM:", p)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
