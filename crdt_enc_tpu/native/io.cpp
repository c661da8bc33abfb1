// File I/O of FsStorage (backends/fs.py is the only caller): the one
// reader of op-file runs, the listing, the writers, and the size scan and
// probe of the replication status.
//
// The reads.
//
// An op log is remote/ops/<actor>/<N> for N = first, first+1, ... until the
// first missing file (the dense-version contract,
// crdt-enc-tokio/src/lib.rs:254-269).  A replica polling its remote makes
// four reads: the names in meta/, in states/ and in ops/, and the dense
// runs of the actors with a new file.  Each is ONE call below
// (`list_dir_names`, `load_op_window`), so that a worker job making all
// four (Core's ingest job) hands the interpreter lock round four times and
// not once a system call, and walks the long <tenant>/remote/ prefix four
// times and not once a file:
//
//   list names     open(dir) -> readdir to the end -> close; names back
//                  NUL-separated in the caller's buffer, `.` and `..`
//                  left out, nothing else judged (the caller filters and
//                  sorts).  An absent directory has no names.
//   load op runs   open(ops) once; per wanted (actor, first version), for
//                  v = first, first+1, ...: openat("<actor>/<v>") [absent:
//                  the run ends; for v = first this is the probe] ->
//                  fstat [not a regular file: the run ends] -> read
//                  st_size bytes -> read again, which must say end of
//                  file -> close.  All bytes land in one buffer, in the
//                  order asked.  Where the buffers are full the call ends
//                  clean and says where, so the next call goes on there.
//
// The folder's chunk iterator reads the same way, a window of wanted
// devices a call.
//
// As with the writers below, a surprise is never handled here: a file
// that is there and cannot be opened or read, one that ends before or
// after its size, a listing the caller's buffer does not hold, a file
// larger than the whole buffer, any other errno, is a non-zero status, and
// fs.py reads on file by file (a listing: from its start), which tells a
// benign race (file gone: the dense run ends) from a defect (file present
// but unreadable: loud).
//
// File steps: the writers (backends/fs.py is the only caller).
//
// Every file step FsStorage makes — an immutable publish, a replace of a
// mutable local file, one GC list — is ONE call below.  ctypes.CDLL
// releases the interpreter lock for its length, so sixteen seal tails on
// sixteen worker threads do not hand the lock round once per system
// call; and every path is relative to a directory opened once, so a step
// walks the long <tenant>/remote/<family>/ prefix once, not eight times
// (on the chip machine's kernel a path walk costs more than a flush).
//
//   publish new    open(dir) [ENOENT only: mkdir -p, open again] ->
//                  openat(.tmp-<32 hex>, O_CREAT|O_EXCL) -> write all ->
//                  fsync(file) -> close -> linkat(tmp, name) ->
//                  unlinkat(tmp) -> fsync(dir) -> close
//   write atomic   the same with renameat for linkat + unlinkat
//   remove lists   open(base) once; per actor openat, readdir,
//                  unlinkat each all-digit name <= last, then
//                  unlinkat(actor, AT_REMOVEDIR), failure ignored
//
// The flushes are the Python helpers' own, in their places: the file
// before its name appears, the directory after, both before the call
// returns.  What a listing can observe is unchanged: final names, the
// `.tmp-` prefix of a file in flight, an emptied actor directory gone.
//
// A surprise is never handled here.  The name exists (an identical
// replay? a burned version?), a directory vanished under a step (the
// compactor of another replica), a directory entry int() might read as a
// number though it is not all digits, any other errno: the step removes
// its tmp, returns a non-zero status, and fs.py runs the Python helper
// from its start.  Those rules (`_write_file_new`'s docstring) decide
// whether a write is lost or doubled; they stay written once, where the
// crash and fault tests patch and count them.  One status is negative:
// the name is published and the directory's flush failed.  A replay
// would find identical content and call it success, so fs.py raises it.

#include <atomic>
#include <cerrno>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <dirent.h>
#include <fcntl.h>
#include <pthread.h>
#include <sys/random.h>
#include <sys/stat.h>
#include <unistd.h>

namespace {

int path_join(char* out, size_t cap, const char* dir, int64_t version) {
  int n = snprintf(out, cap, "%s/%lld", dir, (long long)version);
  return (n > 0 && (size_t)n < cap) ? 0 : -1;
}

// ---- file steps (the writers; protocol in the header comment) ----------

// `.tmp-<32 hex>`: a salt drawn once a process (again in a forked child,
// which would otherwise repeat its parent's names) and a counter, so a
// name costs no system call.  O_EXCL still guards the create.
std::atomic<uint64_t> g_tmp_salt{0};
std::atomic<uint64_t> g_tmp_counter{0};

void draw_tmp_salt() {
  uint64_t salt = 0;
  if (getrandom(&salt, sizeof salt, 0) != (ssize_t)sizeof salt)
    salt = ((uint64_t)getpid() << 32) ^ (uint64_t)(uintptr_t)&salt;
  g_tmp_salt.store(salt);
}

void tmp_name(char out[40]) {
  static const int registered =
      (draw_tmp_salt(), pthread_atfork(nullptr, nullptr, draw_tmp_salt));
  (void)registered;
  snprintf(out, 40, ".tmp-%016llx%016llx",
           (unsigned long long)g_tmp_salt.load(),
           (unsigned long long)g_tmp_counter.fetch_add(1));
}

// Every flush the writers make, counted: a test holds a seal tail to its
// eight, which no Python-side probe can see once the steps run here.
std::atomic<int64_t> g_flushes{0};

int flush(int fd) {
  g_flushes.fetch_add(1, std::memory_order_relaxed);
  return fsync(fd);
}

int open_dir(const char* dir) {
  return open(dir, O_RDONLY | O_DIRECTORY | O_CLOEXEC);
}

// `mkdir -p dir`, reached only after open_dir said ENOENT: the seal tail
// never probes for a directory that is there.
void make_dirs(const char* dir) {
  char path[4096];
  size_t n = strlen(dir);
  if (n == 0 || n >= sizeof path) return;
  memcpy(path, dir, n + 1);
  for (size_t i = 1; i <= n; i++) {
    if (path[i] != '/' && path[i] != '\0') continue;
    char keep = path[i];
    path[i] = '\0';
    mkdir(path, 0777);  // EEXIST and every other failure: the open tells
    path[i] = keep;
  }
}

int write_all(int fd, const uint8_t* data, int64_t len) {
  int64_t done = 0;
  while (done < len) {
    ssize_t w = write(fd, data + done, (size_t)(len - done));
    if (w < 0 && errno == EINTR) continue;
    if (w < 0) return errno;
    if (w == 0) return EIO;
    done += w;
  }
  return 0;
}

// The shared body of the two publishes: tmp + fsync in `dir`, then
// linkat (publish new: EEXIST if the name is there) or renameat (write
// atomic: last writer wins), then fsync of the directory.  Returns 0, a
// positive errno when nothing was published (the tmp is gone again), or
// a NEGATIVE errno when the name is in place but the directory's flush
// failed: the one failure a replay from the start would paper over.
int publish(const char* dir, const char* name, const uint8_t* data,
            int64_t len, bool exclusive) {
  int dfd = open_dir(dir);
  if (dfd < 0 && errno == ENOENT) {
    make_dirs(dir);
    dfd = open_dir(dir);
  }
  if (dfd < 0) return errno;
  char tmp[40];
  tmp_name(tmp);
  int fd = openat(dfd, tmp, O_WRONLY | O_CREAT | O_EXCL | O_CLOEXEC, 0644);
  if (fd < 0) {
    int err = errno;
    close(dfd);
    return err;
  }
  int err = write_all(fd, data, len);
  if (err == 0 && flush(fd) != 0) err = errno;
  if (close(fd) != 0 && err == 0) err = errno;
  if (err == 0) {
    if (exclusive) {
      if (linkat(dfd, tmp, dfd, name, 0) != 0) err = errno;
      unlinkat(dfd, tmp, 0);
    } else if (renameat(dfd, tmp, dfd, name) != 0) {
      err = errno;
      unlinkat(dfd, tmp, 0);
    }
    if (err == 0 && flush(dfd) != 0) err = -errno;
  } else {
    unlinkat(dfd, tmp, 0);
  }
  close(dfd);
  return err;
}

// Would Python's int() take a name that is not all ASCII digits?  Only
// if it holds a digit at all: an ASCII one, or (above 0x7f) perhaps a
// Unicode one.  Such a name is a surprise and goes back to Python.
bool maybe_python_int(const char* name) {
  for (const unsigned char* p = (const unsigned char*)name; *p; p++)
    if ((*p >= '0' && *p <= '9') || *p >= 0x80) return true;
  return false;
}

}  // namespace

extern "C" {

// Sizes of the dense run starting at `first` (the replication status'
// backlog probe).  Writes up to max_files sizes; returns the count of
// consecutive existing files.
int64_t scan_op_sizes(const char* dir, int64_t first, int64_t max_files,
                      int64_t* sizes_out) {
  char path[4096];
  int64_t n = 0;
  for (; n < max_files; n++) {
    if (path_join(path, sizeof(path), dir, first + n) != 0) return n;
    struct stat st;
    if (stat(path, &st) != 0 || !S_ISREG(st.st_mode)) return n;
    sizes_out[n] = (int64_t)st.st_size;
  }
  return n;
}

// Warm-open tail probe: does remote/ops/<actor>/<first> exist, for many
// actors in one call.  `rel_paths` is a flat NUL-separated buffer of n
// entries ("<actor-hex>/<version>"); out_mask[i] = 1 when the file
// exists.  dirfd-relative so each access resolves two path components
// instead of re-walking the whole remote prefix — on containerized
// kernels every syscall costs ~100µs+, so the probe is one syscall per
// actor and zero interpreter overhead.  Returns n, or -1 when base_dir
// cannot be opened (caller falls back to per-actor Python stats).
int64_t probe_op_files(const char* base_dir, int64_t n,
                       const char* rel_paths, uint8_t* out_mask) {
  int dfd = open(base_dir, O_RDONLY | O_DIRECTORY);
  if (dfd < 0) return -1;
  const char* p = rel_paths;
  for (int64_t i = 0; i < n; i++) {
    out_mask[i] = faccessat(dfd, p, F_OK, 0) == 0 ? 1 : 0;
    p += strlen(p) + 1;
  }
  close(dfd);
  return n;
}

// ---- the reads: one call a read (protocol in the header) ------------

// Names of the entries of `dir`, NUL-separated into `buf` (`cap` bytes);
// their count in *n_out, the bytes used in *used_out.  An absent
// directory is an empty one.  ERANGE when `buf` does not hold them.
int32_t list_dir_names(const char* dir, char* buf, int64_t cap,
                       int64_t* n_out, int64_t* used_out) {
  *n_out = *used_out = 0;
  int dfd = open_dir(dir);
  if (dfd < 0) return errno == ENOENT ? 0 : errno;
  DIR* listing = fdopendir(dfd);  // owns dfd from here
  if (listing == nullptr) {
    int err = errno;
    close(dfd);
    return err;
  }
  int status = 0;
  int64_t n = 0, used = 0;
  for (;;) {
    errno = 0;
    struct dirent* e = readdir(listing);
    if (e == nullptr) {
      status = errno;
      break;
    }
    const char* name = e->d_name;
    if (strcmp(name, ".") == 0 || strcmp(name, "..") == 0) continue;
    int64_t len = (int64_t)strlen(name) + 1;
    if (used + len > cap) {
      status = ERANGE;
      break;
    }
    memcpy(buf + used, name, (size_t)len);
    used += len;
    n++;
  }
  closedir(listing);
  *n_out = n;
  *used_out = used;
  return status;
}

// The dense runs of n wanted (actor, first version) pairs under
// `ops_dir` (`actors`: flat NUL-separated directory names), every path
// relative to the one descriptor, as far as the buffers hold them.
// counts_out[i] is the length of pair i's run (0: nothing new, the
// probe's answer); sizes_out holds the file sizes of all runs in order
// (at most max_files), `buf` their bytes back to back (at most cap);
// *files_out and *bytes_out the totals.  An absent ops_dir, actor
// directory or first file is an empty run.  Any file that is there and
// does not read back whole at its size is EIO or its errno.  Buffers that
// do not hold the runs: the call ends clean before the file that does not
// fit, *stop_out the pair it is in (counts_out says how far into its run;
// n when every run ended), and the next call resumes there; only a first
// file that alone overflows `buf` is ERANGE.
int32_t load_op_window(const char* ops_dir, int64_t n, const char* actors,
                       const int64_t* firsts, int64_t max_files, int64_t cap,
                       int64_t* counts_out, int64_t* sizes_out, uint8_t* buf,
                       int64_t* files_out, int64_t* bytes_out,
                       int64_t* stop_out) {
  *files_out = *bytes_out = 0;
  *stop_out = n;
  for (int64_t i = 0; i < n; i++) counts_out[i] = 0;
  int ofd = open_dir(ops_dir);
  if (ofd < 0) return errno == ENOENT ? 0 : errno;
  int status = 0;
  int64_t files = 0, used = 0, stop = n;
  const char* actor = actors;
  char rel[320];
  for (int64_t i = 0; i < n && status == 0 && stop == n;
       i++, actor += strlen(actor) + 1) {
    for (int64_t v = firsts[i];; v++) {
      int len = snprintf(rel, sizeof rel, "%s/%lld", actor, (long long)v);
      if (len <= 0 || (size_t)len >= sizeof rel) {
        status = ENAMETOOLONG;
        break;
      }
      // O_NONBLOCK: a FIFO where a version should be must not hang the
      // open; it is not a regular file and ends the run below
      int fd = openat(ofd, rel, O_RDONLY | O_NONBLOCK | O_CLOEXEC);
      if (fd < 0) {
        // no such file, or no such actor directory (or a junk file of
        // its name): the dense run ends here
        if (errno != ENOENT && errno != ENOTDIR) status = errno;
        break;
      }
      struct stat st;
      if (fstat(fd, &st) != 0) {
        status = errno;
        close(fd);
        break;
      }
      if (!S_ISREG(st.st_mode)) {
        close(fd);
        break;
      }
      int64_t want = (int64_t)st.st_size;
      if (files >= max_files || used + want > cap) {
        if (files > 0) stop = i;
        else status = ERANGE;
        close(fd);
        break;
      }
      int64_t got = 0;
      while (got < want) {
        ssize_t r = read(fd, buf + used + got, (size_t)(want - got));
        if (r < 0 && errno == EINTR) continue;
        if (r < 0) status = errno;
        if (r == 0) status = EIO;  // shorter than its size
        if (r <= 0) break;
        got += r;
      }
      if (status == 0) {
        // an op file is immutable once published: it ends where its
        // size says
        uint8_t extra;
        ssize_t tail;
        do {
          tail = read(fd, &extra, 1);
        } while (tail < 0 && errno == EINTR);
        if (tail != 0) status = tail < 0 ? errno : EIO;
      }
      close(fd);
      if (status != 0) break;
      sizes_out[files++] = want;
      used += want;
      counts_out[i]++;
    }
  }
  close(ofd);
  *files_out = files;
  *bytes_out = used;
  *stop_out = stop;
  return status;
}

// ---- the writers: one call a file step --------------------------------
//
// Each returns 0 for the clean path and a non-zero status for everything
// else; the caller (backends/fs.py) then runs the Python helper from its
// start.  Nothing below is left behind by a non-zero return but, at most,
// the published name itself.

int64_t file_step_flushes() { return g_flushes.load(); }

// Immutable publish of dir/name (content- or version-addressed).
int32_t publish_file_new(const char* dir, const char* name,
                         const uint8_t* data, int64_t len) {
  return publish(dir, name, data, len, true);
}

// Last-writer-wins replace of dir/name (local meta, local checkpoint).
int32_t write_file_atomic(const char* dir, const char* name,
                          const uint8_t* data, int64_t len) {
  return publish(dir, name, data, len, false);
}

// Every version <= lasts[i] of base_dir/<actors[i]>/, for n actors in one
// call (`actors`: flat NUL-separated directory names), then rmdir of the
// actor directory, which fails harmlessly while files remain.  An absent
// base or actor directory has nothing to remove.  `.tmp-` files, `.`,
// `..` and names int() cannot read are skipped, as the Python body skips
// them; a name int() MIGHT read differently from strtoll ("+7", "1_0",
// twenty digits, non-ASCII) is left in place and reported as EINVAL.
int32_t remove_log_prefixes(const char* base_dir, int64_t n,
                            const char* actors, const int64_t* lasts) {
  int bfd = open_dir(base_dir);
  if (bfd < 0) return errno == ENOENT ? 0 : errno;
  int status = 0;
  const char* actor = actors;
  for (int64_t i = 0; i < n && status == 0; i++, actor += strlen(actor) + 1) {
    int afd = openat(bfd, actor, O_RDONLY | O_DIRECTORY | O_CLOEXEC);
    if (afd < 0) {
      if (errno != ENOENT) status = errno;
      continue;
    }
    DIR* listing = fdopendir(afd);  // owns afd from here
    if (listing == nullptr) {
      status = errno;
      close(afd);
      continue;
    }
    for (;;) {
      errno = 0;
      struct dirent* e = readdir(listing);
      if (e == nullptr) {
        if (errno != 0) status = errno;
        break;
      }
      const char* name = e->d_name;
      if (strncmp(name, ".tmp-", 5) == 0 || strcmp(name, ".") == 0 ||
          strcmp(name, "..") == 0)
        continue;
      size_t digits = strspn(name, "0123456789");
      if (name[digits] != '\0' || digits == 0 || digits > 18) {
        if (maybe_python_int(name)) status = EINVAL;
        continue;
      }
      if (strtoll(name, nullptr, 10) > lasts[i]) continue;
      if (unlinkat(afd, name, 0) != 0 && errno != ENOENT) {
        status = errno;
        break;
      }
    }
    closedir(listing);
    unlinkat(bfd, actor, AT_REMOVEDIR);
  }
  close(bfd);
  return status;
}

// dir/<names[i]> for n names (flat NUL-separated), already-gone files
// tolerated: the content-addressed families' GC.
int32_t remove_names(const char* dir, int64_t n, const char* names) {
  int dfd = open_dir(dir);
  if (dfd < 0) return errno == ENOENT ? 0 : errno;
  int status = 0;
  const char* name = names;
  for (int64_t i = 0; i < n; i++, name += strlen(name) + 1) {
    if (unlinkat(dfd, name, 0) != 0 && errno != ENOENT) {
      status = errno;
      break;
    }
  }
  close(dfd);
  return status;
}

}  // extern "C"
